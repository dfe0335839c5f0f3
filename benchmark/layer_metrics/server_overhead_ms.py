"""server layer: what the HTTP path adds around the engine's own wall —
median of (client latency - that query's wallMillis)."""
import statistics


def read(ctx):
    gaps = [r["latency_s"] * 1e3 - r["info"]["wallMillis"]
            for r in ctx["requests"] if r.get("info")]
    return statistics.median(gaps) if gaps else None
