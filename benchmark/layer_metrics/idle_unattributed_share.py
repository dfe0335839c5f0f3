"""executor: share of the slice's device-idle seconds whose middle no
`planning`, `execution`, `compile` or `result_fetch` span of any request
covers (`stats.spans`, on the clock the trace is tied to): the idle time
that is nobody's — requests queued with none running, or no request at
all. `trace_programs.py` keeps the split by covering span."""
import trace_programs


def read(ctx):
    t = trace_programs.table(ctx)
    if not t or t["idle_s"] <= 0 or not any(
            r["info"]["stats"].get("spans")
            for r in trace_programs.executed(ctx)):
        return None         # no device plane, or a program without spans
    idle = t["idle_by_span"]
    nobody = idle.get("queued_only", 0.0) + idle.get("no_request", 0.0)
    return 100.0 * nobody / sum(idle.values())
