"""executor: the host's cost of one enqueue, in microseconds — the
executed queries' summed `stats.host_ms["kernel_call"]` (signature, AOT
lookup, notes, the call; self time, a compile inside it taken out) ÷ their
summed `stats.host_calls["kernel_call"]`. Listed only for the cells the
host paces (the scan cell, the dashboard): where the device queue is full
the call blocks and this would read the device's time, not the host's.
None for a program without the activities."""
import trace_programs


def read(ctx):
    stats = [r["info"]["stats"] for r in trace_programs.executed(ctx)
             if "host_calls" in r["info"]["stats"]]
    calls = sum(s["host_calls"].get("kernel_call", 0) for s in stats)
    if not calls:
        return None
    return 1e3 * sum(s["host_ms"].get("kernel_call", 0.0)
                     for s in stats) / calls
