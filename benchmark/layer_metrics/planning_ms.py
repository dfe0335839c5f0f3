"""frontend layer: median parse + plan time of the queries that executed
(a result-cache hit plans nothing)."""
import statistics


def read(ctx):
    plans = [r["info"]["stats"]["planning_s"] * 1e3 for r in ctx["requests"]
             if r.get("info") and r["info"].get("stats")
             and not r["info"]["stats"]["result_cache_hits"]]
    return statistics.median(plans) if plans else None
