"""executor layer: kernels the window's queries did not find in the jit
cache. Anything but 0 means the warm-up missed a shape."""


def read(ctx):
    stats = [r["info"]["stats"] for r in ctx["requests"]
             if r.get("info") and r["info"].get("stats")]
    return sum(s["jit_misses"] for s in stats) if stats else None
