"""executor: queries of the window that memory pressure hurt — times the
node pool's low-memory killer chose one as its victim (`stats.memory_kills`)
plus device allocations XLA refused (`stats.device_oom_errors`). Like
`compiles_in_window`, anything but 0 is a finding: three joins share the
chip at 95 % of its memory, and under the default `retry_policy` NONE
either one is a failed request. None for a program without the counters."""


def read(ctx):
    stats = [r["info"]["stats"] for r in ctx["requests"]
             if r.get("info") and "device_oom_errors" in r["info"]["stats"]]
    if not stats:
        return None
    return sum(s["memory_kills"] + s["device_oom_errors"] for s in stats)
