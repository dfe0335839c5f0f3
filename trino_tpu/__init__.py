"""trino_tpu — a TPU-native distributed SQL analytics engine.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of Trino
(reference surveyed in SURVEY.md): SQL frontend -> cost-based optimizer ->
plan fragments compiled to jit/shard_map programs over a TPU mesh, with
columnar Pages as pytrees and ICI collectives as the exchange data plane.
"""

__version__ = "0.1.0"

import jax as _jax

# SQL semantics require 64-bit lanes (BIGINT keys, DOUBLE aggregation,
# microsecond timestamps); JAX defaults to 32-bit. Engine-wide x64 is a
# correctness requirement; kernels narrow to int32/bf16 where the planner
# proves it safe (e.g. dictionary codes, date arithmetic).
_jax.config.update("jax_enable_x64", True)

def enable_persistent_cache() -> None:
    """Turn on XLA's persistent compilation cache, by one rule: where
    $JAX_COMPILATION_CACHE_DIR is set the directory is JAX's own business
    (it reads that variable itself; nothing is set in code), otherwise the
    cache lives at the fixed `<checkout>/.jax_cache` beside the package —
    fixed because the path is part of the cache key, so a directory that
    moves never hits. Query kernels are expensive to compile (a sort-bearing
    program costs the TPU compiler minutes) and keyed purely by program;
    caching them on disk makes repeat runs — test suites, restarted
    servers, a second chip_smoke.py — skip recompilation. With literal
    hoisting (expr/hoist.py) kernels are literal-free, so one disk entry
    serves every literal variant of a query shape across processes; the
    in-process jit-cache LRU sits above this, holding loaded executables
    (an LRU eviction costs a re-trace + disk load, not a recompile)."""
    import os as _os
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _jax.config.update("jax_compilation_cache_dir", _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache"))
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


from trino_tpu import types
from trino_tpu.page import Column, Dictionary, Page
