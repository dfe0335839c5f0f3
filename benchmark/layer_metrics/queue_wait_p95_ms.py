"""executor: 95th percentile of `stats.queued_ms` — submit to an executor
thread taking the query (`server/app.py` `_drain`) — over the window's
executed (non-hit) queries. Beside `latency_p95_ms` it says whether the
dashboard's tail is waiting for a thread or running among others."""
import math

import trace_programs


def read(ctx):
    waits = sorted(r["info"]["stats"]["queued_ms"]
                   for r in trace_programs.executed(ctx)
                   if "queued_ms" in r["info"]["stats"])
    return waits[math.ceil(0.95 * len(waits)) - 1] if waits else None
