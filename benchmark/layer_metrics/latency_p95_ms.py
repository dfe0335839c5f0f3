"""executor layer: 95th percentile of client latency over every request
of the window, hits and misses alike (a failed request counts as the
window's length). In a saturated closed loop it is the queue of misses
behind one device that it shows (`max_running`, S6), and it swings by
~10 % from run to run: a layer's metric, not a bound's."""
import math


def read(ctx):
    lat = sorted(r["latency_s"] for r in ctx["requests"])
    return 1e3 * lat[math.ceil(0.95 * len(lat)) - 1] if lat else None
