"""executor: share of the slice's device-idle seconds that the host
timeline still cannot name — an executor thread was inside `execution`
and under no `host__*` activity (`interpreter`: generators, `Page`
construction, the collector's bookkeeping) — ÷ all idle seconds of
`host_timeline.idle_by_activity`. Not listed for the mesh cell: its slice
lies inside one query's wait on the chips, the profiler keeps only what
began and ended inside the session, and no `host__*` event is left to
split. None without a device plane, without an idle second, or for a
program without the activities."""
import host_timeline


def read(ctx):
    t = host_timeline.table(ctx)
    if not t or t["idle_s"] <= 0:
        return None
    return 100.0 * t["idle_by_activity"].get(
        host_timeline.INTERPRETER, 0.0) / t["idle_s"]
