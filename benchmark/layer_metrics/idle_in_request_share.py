"""executor layer: share of the traced slice in which the device was idle
although a request was in flight — the time the executor, the planner and
the result path take between programs. (The program's own
`stats.host_time_ms` is no such thing: see PERF.md, Findings, PR 24.)"""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    return 100.0 * trace["idle_in_request_s"] / trace["window_s"]
