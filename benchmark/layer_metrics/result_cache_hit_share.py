"""serve caches: requests of the window answered from the result cache,
as a share of those whose query info could be read."""


def read(ctx):
    stats = [r["info"]["stats"] for r in ctx["requests"]
             if r.get("info") and r["info"].get("stats")]
    if not stats:
        return None
    return 100.0 * sum(s["result_cache_hits"] for s in stats) / len(stats)
