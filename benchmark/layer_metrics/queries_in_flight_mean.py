"""executor: how many requests' `execution` spans cover an instant, as a
time-average from the first request sent to the last answer drained
(`stats.spans`, on the load generator's clock). A state of the executor
pool, not a score: 1.0 says the server runs the streams' queries one
after another, 2 and more that they overlap on the one device queue —
which buys no device time and costs a query's memory each; under 1.0
nothing was executing for part of the time. None for a program without
spans."""
import trace_programs


def read(ctx):
    lo = min((r["t_send"] for r in ctx["requests"]), default=None)
    hi = max((r["t_done"] for r in ctx["requests"]), default=None)
    spans = [(start, end) for r in trace_programs.executed(ctx)
             for name, start, end in r["info"]["stats"].get("spans") or []
             if name == "execution"]
    if not spans or hi <= lo:
        return None
    covered = sum(max(0.0, min(end, hi) - max(start, lo))
                  for start, end in spans)
    return covered / (hi - lo)
