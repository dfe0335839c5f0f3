"""serve caches: host-to-device staging per executed query; 0 when every
table the window reads is resident on the device."""


def read(ctx):
    staged = [r["info"]["stats"]["scan_staging_bytes"]
              for r in ctx["requests"]
              if r.get("info") and r["info"].get("stats")
              and not r["info"]["stats"]["result_cache_hits"]]
    return sum(staged) / len(staged) / 1e6 if staged else None
