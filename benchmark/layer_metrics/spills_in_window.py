"""executor: how often the window's executed queries left the device for
the spill ladder (exec/spill.py): each query's `spill_fallbacks`, plus one
for a query that spilled any bytes to the host. A spilled answer is still
exact; anything but 0 is a finding, as with compiles_in_window."""
import trace_programs


def read(ctx):
    stats = [r["info"]["stats"] for r in trace_programs.executed(ctx)]
    stats = [s for s in stats if "spilled_bytes" in s]
    if not stats:
        return None
    return sum(s.get("spill_fallbacks", 0) + (s["spilled_bytes"] > 0)
               for s in stats)
