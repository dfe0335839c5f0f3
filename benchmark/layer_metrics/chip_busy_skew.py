"""device: how unevenly the cell's chips were busy in the traced slice —
the busiest chip's busy seconds over the mean of all of them
(`trace_reduce`'s `busy_s_per_chip`, the `chips` line's). 1.0 is an even
mesh; a table that lands on one chip, or one chip doing the others'
generation, reads above it (the SF1 rehearsal of PR 27: 9.75 / 7.10 =
1.37)."""


def read(ctx):
    busy = (ctx.get("trace") or {}).get("busy_s_per_chip")
    if not busy or len(busy) < 2 or sum(busy) <= 0:
        return None         # one chip, no device plane, or nothing ran
    return max(busy) / (sum(busy) / len(busy))
