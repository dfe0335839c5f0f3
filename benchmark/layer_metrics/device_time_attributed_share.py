"""ops kernels: share of the slice's device-busy seconds that the trace
gives to a named operator — an op whose scope path or whose program
carries a `<family>__<tag>` name (`trace_programs.py`). What is left is
work nobody can plan an optimisation from."""
import trace_programs


def read(ctx):
    t = trace_programs.table(ctx)
    if not t or t["busy_s"] <= 0 \
            or not any(f in t["by_family"] for f in trace_programs.FAMILIES):
        return None         # no device plane, or a program without names
    named = sum(v for k, v in t["by_family"].items() if k != "unattributed")
    return 100.0 * named / sum(t["by_family"].values())
