"""exchange: share of a chip's busy seconds in the traced slice in which
the chip did nothing but a collective — the exposed time in the wire,
the mean over the cell's chips. A collective is an `XLA Ops` event whose
instruction is `all-to-all`, `all-gather`, `all-reduce`,
`collective-permute` or `reduce-scatter`; an asynchronous one counts from
its `-start` to its `-done`. From those intervals whatever another op of
the same chip covers is taken away (work that hid the wire), and what is
left is divided by the chip's busy seconds, which here include it."""
import os
import re

import trace_programs
import trace_reduce

COLLECTIVE = re.compile(
    r"^(all-to-all|all-gather|all-reduce|collective-permute|reduce-scatter)"
    r"(-start|-done)?$")


def opcode(hlo: str) -> str:
    """'%all-gather-start.3 = (...) all-gather-start(...)' -> its opcode."""
    head, _, rest = hlo.partition(" = ")
    found = re.search(r"[\s)}\]]([a-z][\w-]*)\(", " " + rest)
    # a name alone ('%all-to-all.12') says as much
    return found.group(1) if found else re.sub(r"^%|\.\d+$", "",
                                               head.strip())


def split(events: list, lo: int, hi: int):
    """-> (collective intervals, other ops' intervals) inside [lo, hi);
    `events`: (metadata, start, duration) of one chip's `XLA Ops` line."""
    wire, other, open_starts = [], [], {}
    for meta, start, duration in sorted(events, key=lambda e: e[1]):
        a, b = max(start, lo), min(start + duration, hi)
        kind = COLLECTIVE.match(opcode(meta["name"]))
        if not kind:
            if b > a:
                other.append((a, b))
            continue
        if kind.group(2) == "-start":
            open_starts.setdefault(kind.group(1), []).append(start)
        elif kind.group(2) == "-done" and open_starts.get(kind.group(1)):
            a = max(open_starts[kind.group(1)].pop(0), lo)
        if b > a:
            wire.append((a, b))
    return wire, other


def exposed(wire: list, other: list) -> int:
    """ns of the union of `wire` that no interval of `other` covers."""
    both = trace_reduce._union(wire + other)
    return both - trace_reduce._union(other)


def read(ctx):
    # where run.py keeps the traced slice, as trace_programs.table finds it
    path = trace_programs.newest_xplane(os.path.join(os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        ".bench_out"))
    if not ctx.get("trace") or not ctx.get("slice") or not path:
        return None
    planes = trace_programs.read_xspace(path)
    lo = hi = None
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for meta, start, _ in line["events"]:
                if meta["name"] == trace_reduce.BEGIN and lo is None:
                    lo = start
                elif meta["name"] == trace_reduce.END and hi is None:
                    hi = start
    if lo is None or hi is None or hi <= lo:
        return None
    shares, saw_one = [], False
    for chip in ctx["chips"]:
        events = [e for p in planes
                  if p["name"] == trace_reduce.plane_name(chip)
                  for line in p["lines"]
                  if line["name"] == trace_reduce.OPS_LINE
                  for e in line["events"]]
        wire, other = split(events, lo, hi)
        saw_one = saw_one or bool(wire)
        busy = trace_reduce._union(wire + other)
        if busy:
            shares.append(exposed(wire, other) / busy)
    if not saw_one:
        return None         # one chip's programs hold no collective
    return 100.0 * sum(shares) / len(ctx["chips"])
