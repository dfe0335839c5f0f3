"""ops kernels: the least time the chip could take to read the bytes the
traced slice's executed queries must read (each named column once, at
the width the engine stores it: the shape's `needed_bytes`) — all the
cell's chips reading at once, each at its peak — over the seconds a
chip was busy in the slice, the mean over them. HBM-bound by construction —
these shapes do a few integer operations per byte. A request that lies
partly in the slice counts by the share of its time that does."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    lo, hi = ctx["slice"]
    needed = 0.0
    for r in ctx["requests"]:
        stats = (r.get("info") or {}).get("stats")
        if not stats or stats["result_cache_hits"]:
            continue
        overlap = min(r["t_done"], hi) - max(r["t_send"], lo)
        if overlap > 0:
            needed += ctx["shapes"][r["shape"]].needed_bytes(
                ctx["config"]["rows"], ctx["config"]["column_bytes"]) \
                * overlap / (r["t_done"] - r["t_send"])
    share = 100.0 * needed \
        / (len(ctx["chips"]) * ctx["peaks"]["hbm_bytes_per_s"]) \
        / trace["busy_s"]
    if share > 100.0:
        raise ValueError(
            f"query_hbm_roofline {share:.1f} % is above 100: bytes are "
            "counted too high or busy time leaves out part of the work")
    return share if needed else None
