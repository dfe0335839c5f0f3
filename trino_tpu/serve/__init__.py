"""Serving tier: the high-QPS production front door.

The subsystem above the execution engine that makes a repeated prepared
statement cost approximately one HTTP round trip:

- `serve/streaming.py` — bounded result ring buffers behind the async
  streaming statement lifecycle (QUEUED -> RUNNING -> FINISHING):
  result pages reach the client as operators produce them, and a slow
  client pauses the producer at a cooperative checkpoint instead of
  buffering the full result.
- `serve/caches.py` — the result-set cache and the table-scan page
  cache, keyed on plan fingerprint and evicted through the SAME
  invalidation call DDL/INSERT drives into the plan cache
  (exec/plan_cache.py hooks), so a cached result can never outlive a
  table change.
- `serve/warmup.py` — the warmup/preload manifest: statements PREPAREd
  and pre-executed at server startup so the first real user request hits
  a warm plan cache and warm (persistent-compilation-cache-backed)
  kernels.
- `serve/bench_serve.py` — a closed-loop QPS benchmark over prepared
  EXECUTEs (`run_qps_bench`).
"""

from trino_tpu.serve.caches import (CachedResult, ResultSetCache,  # noqa: F401
                                    ScanCache, result_cache_stats,
                                    scan_cache_stats,
                                    statement_is_cacheable)
from trino_tpu.serve.streaming import ResultStream, stream_stats  # noqa: F401
from trino_tpu.serve.warmup import apply_warmup, load_manifest  # noqa: F401
