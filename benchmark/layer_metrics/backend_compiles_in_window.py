"""executor: executables XLA compiled for the window's queries, the sum
of `stats.backend_compiles` — counted where XLA reports a compile
(`jit_cache`'s `jax.monitoring` listener, on the thread that compiled),
so a cached kernel retraced for new avals counts, which
`compiles_in_window` (cache keys) cannot see; a reload from the
persistent compilation cache does not (`stats.backend_cache_loads`).
Anything but 0 in a warm window is a finding, and `stats.backend_compiled`
names the programs. None for a program without the counter."""


def read(ctx):
    stats = [r["info"]["stats"] for r in ctx["requests"]
             if r.get("info") and "backend_compiles" in r["info"]["stats"]]
    return sum(s["backend_compiles"] for s in stats) if stats else None
