"""Whose device time is it: the traced slice by program and operator.

`trace_reduce.py` says how long the chip was busy and names operations as
XLA numbered them (`%fusion.14`), per module, so one name adds different
operations together. Since PR 25 the engine names every program it jits
(`<family>__<tag>...`, `exec/jit_cache.program_name`) and wraps each
operator's phases in `jax.named_scope` with the same grammar. Both arrive
in the xplane with no further machinery: the `XLA Modules` line of a
device plane has one event per program run, and every `XLA Ops` event's
metadata keeps the op's scope path. This module reads them back:

  reduce(path, spans, t_begin, chips)
                                -> the table below over the planes of the
                                   cell's chips (device ids), or None
                                   without one (a CPU rehearsal)
  table(ctx)                    -> the same for the xplane this process
                                   wrote, cached on `ctx` and kept whole
                                   as .bench_out/trace_programs.json

  busy_s          as trace_reduce's: union of the op intervals, the mean
                  over the cell's chips — as are the seconds of
                  by_owner, by_family and top_ops; idle is taken where
                  none of the chips ran an operation
  by_owner        {"<program>/<scope>": seconds}; an op's owner is the
                  innermost scope of its path that matches the grammar,
                  else its module's name if that does, else `unattributed`
  by_family       seconds per family (the owner's part before `__`),
                  plus `unattributed`
  top_ops         the ten (owner, instruction as trace_reduce names it)
                  with most seconds: which operator `%fusion.14` is
  modules         names on the `XLA Modules` line inside the slice
  idle_s          slice - busy
  idle_by_span    idle seconds by the first of `compile`, `result_fetch`,
                  `planning`, `dispatch` (an `execution` span's self time)
                  that covers the middle of the gap, else `queued_only`
                  (requests wait and none runs), else `no_request`

`jax.profiler.ProfileData` shows per-event stats only, not the event
metadata's, and no xplane_pb2 is installed: the file is read with a small
decoder of the protobuf wire format, for the five message kinds needed.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import struct

import trace_reduce

GRAMMAR = re.compile(
    r"^(scan_filter|aggregate|join|sort|window|exchange|misc)"
    r"__[a-z0-9]+(?:_[a-z0-9]+)*$")
FAMILIES = ("scan_filter", "aggregate", "join", "sort", "window",
            "exchange", "misc")
MODULES_LINE = "XLA Modules"
SPAN_ORDER = ("compile", "result_fetch", "planning", "dispatch")

# --------------------------------------------------------------- wire format


def _varint(buf, at):
    out = shift = 0
    while True:
        b = buf[at]
        at += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, at
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; length-delimited
    values come back as memoryview slices, not copies."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 1:
            value, at = buf[at:at + 8], at + 8
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == 5:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield number, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= (1 << 63) else v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """XStat -> (name, value); a ref_value is resolved to its string."""
    name, value = None, None
    for number, wire, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number in (5, 6):
            value = _text(v)
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key, value = 0, b""
    for number, _, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _event_metadata(buf, stat_names):
    meta = {"name": "", "display_name": "", "stats": {}}
    for number, _, v in _fields(buf):
        if number == 2:
            meta["name"] = _text(v)
        elif number == 4:
            meta["display_name"] = _text(v)
        elif number == 5:
            k, val = _stat(v, stat_names)
            meta["stats"][k] = val
    return meta


def _line(buf):
    line = {"name": "", "timestamp_ns": 0, "events": []}
    for number, _, v in _fields(buf):
        if number == 2:
            line["name"] = _text(v)
        elif number == 3:
            line["timestamp_ns"] = _signed(v)
        elif number == 4:
            meta = offset = duration = 0
            for n2, _, v2 in _fields(v):
                if n2 == 1:
                    meta = v2
                elif n2 == 2:
                    offset = _signed(v2)
                elif n2 == 3:
                    duration = _signed(v2)
            line["events"].append((meta, offset, duration))
    return line


def read_xspace(path: str, plane_prefixes=("/device:", "/host:")) -> list:
    """[{name, lines: [{name, events: [(metadata, start_ns, duration_ns)]}]}]
    with `metadata` = {name, display_name, stats}; times as
    `ProfileData` gives them: whole ns, line timestamp + offset."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    planes = []
    for number, _, plane_buf in _fields(data):
        if number != 1:
            continue
        name, lines, metas, stat_bufs = "", [], [], []
        for n2, _, v in _fields(plane_buf):
            if n2 == 2:
                name = _text(v)
            elif n2 == 3:
                lines.append(v)
            elif n2 == 4:
                metas.append(v)
            elif n2 == 5:
                stat_bufs.append(v)
        if not name.startswith(tuple(plane_prefixes)):
            continue
        stat_names = {}
        for buf in stat_bufs:
            key, value = _map_entry(buf)
            for n3, _, v in _fields(value):
                if n3 == 2:
                    stat_names[key] = _text(v)
        # stat_names first: an event's metadata refers to them
        event_meta = {}
        for buf in metas:
            key, value = _map_entry(buf)
            event_meta[key] = _event_metadata(value, stat_names)
        plane = {"name": name, "lines": []}
        for buf in lines:
            line = _line(buf)
            t0 = line["timestamp_ns"]
            plane["lines"].append({"name": line["name"], "events": [
                (event_meta.get(m, {"name": str(m), "display_name": "",
                                    "stats": {}}),
                 t0 + offset // 1000, duration // 1000)
                for m, offset, duration in line["events"]]})
        planes.append(plane)
    return planes


# ------------------------------------------------------------------ owners

def scope_path(meta: dict) -> str:
    """The op's `op_name` ("jit(join__uprobe)/jit(main)/join__probe_lookup/
    gather:"), which the TPU profiler keeps as the stat `tf_op` of the
    event's metadata; failing that, the longest stat that looks like one."""
    stats = meta["stats"]
    if isinstance(stats.get("tf_op"), str):
        return stats["tf_op"]
    best = ""
    for value in stats.values():
        if isinstance(value, str) and "/" in value and "jit(" in value \
                and len(value) > len(best):
            best = value
    return best


def program_of(module: str) -> str:
    """'jit_join__uprobe(123456)' -> 'join__uprobe'."""
    program = re.sub(r"\(.*$", "", module)
    return program[4:] if program.startswith("jit_") else program


def owner_of(path: str, module: str):
    """-> (owner "<program>/<scope>", family) by the rule of the
    docstring. `module` is the containing `XLA Modules` event's name,
    `jit_<program>(<fingerprint>)`, or "" outside every module event
    (the owner's label then takes the path's own `jit(...)` root)."""
    program = program_of(module)
    scopes = [p for p in path.rstrip(":").split("/") if GRAMMAR.match(p)]
    if scopes:
        root = re.match(r"jit\(([^)]*)\)", path)
        label = program or (root.group(1) if root else "?")
        return f"{label}/{scopes[-1]}", scopes[-1].split("__")[0]
    if GRAMMAR.match(program):
        return f"{program}/-", program.split("__")[0]
    return f"unattributed:{program or '?'}", "unattributed"


def _module_at(modules: list, starts: list, t: float) -> str:
    """Name of the module event containing time t (events of one line do
    not overlap), or ""."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i][1]:
        return modules[i][2]
    return ""


def self_time_spans(spans: list) -> list:
    """[(name, start, end)] with `execution` replaced by its self time:
    `dispatch` pieces where no `compile`/`result_fetch` child covers."""
    out = []
    children = sorted((s, e) for n, s, e in spans
                      if n in ("compile", "result_fetch"))
    for name, start, end in spans:
        if name != "execution":
            out.append((name, start, end))
            continue
        at = start
        for s, e in children:
            if e <= at or s >= end:
                continue
            if s > at:
                out.append(("dispatch", at, s))
            at = max(at, e)
        if end > at:
            out.append(("dispatch", at, end))
    return out


def reduce(path: str, spans_by_query: list, t_begin: float, chips=(0,)):
    """`spans_by_query`: one list of [name, start, end] (on
    time.monotonic(), `stats.spans` of a query) per executed query;
    `t_begin`: that clock at `bench_slice_begin`; `chips`: the ids of the
    cell's devices."""
    planes = read_xspace(path)
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
        raise ValueError(f"{path}: slice annotations missing ({lo}, {hi})")
    mine = {trace_reduce.plane_name(chip) for chip in chips}
    devices = [p for p in planes if p["name"] in mine]
    if not devices:
        return None
    n_chips = len(chips)    # one of them without a plane ran nothing
    by_owner, by_family, by_op = {}, {}, {}
    intervals, busy, module_names = [], 0.0, set()
    for device in devices:
        modules, ops = [], []
        for line in device["lines"]:
            if line["name"] == MODULES_LINE:
                modules = sorted((s, s + d, m["name"])
                                 for m, s, d in line["events"])
            elif line["name"] == trace_reduce.OPS_LINE:
                ops = line["events"]
        starts = [m[0] for m in modules]
        module_names |= {m[2] for m in modules if m[1] > lo and m[0] < hi}
        own = []
        for meta, start, duration in ops:
            a, b = max(start, lo), min(start + duration, hi)
            if b <= a:
                continue
            own.append((a, b))
            owner, family = owner_of(scope_path(meta),
                                     _module_at(modules, starts, start))
            seconds = (b - a) * 1e-9 / n_chips
            by_owner[owner] = by_owner.get(owner, 0.0) + seconds
            by_family[family] = by_family.get(family, 0.0) + seconds
            op = (owner, trace_reduce.short_name(meta["name"]))
            by_op[op] = by_op.get(op, 0.0) + seconds
        busy += trace_reduce._union(own) * 1e-9 / n_chips
        intervals += own
    # gaps in which no chip of the cell's ran, laid on the requests'
    # spans through the begin stamp
    to_mono = lambda ns: t_begin + (ns - lo) * 1e-9  # noqa: E731
    flat = []
    for spans in spans_by_query:
        flat.extend(self_time_spans([tuple(s) for s in spans]))
    idle_by_span = {}
    for a, b in trace_reduce._gaps(intervals, lo, hi):
        mid = to_mono((a + b) / 2)
        covering = {n for n, s, e in flat if s <= mid <= e}
        name = next((n for n in SPAN_ORDER if n in covering),
                    "queued_only" if "queued" in covering else "no_request")
        idle_by_span[name] = idle_by_span.get(name, 0.0) + (b - a) * 1e-9
    return {
        "busy_s": busy, "window_s": (hi - lo) * 1e-9,
        "idle_s": (hi - lo) * 1e-9 - busy,
        "by_owner": by_owner, "by_family": by_family,
        "idle_by_span": idle_by_span,
        "top_ops": [[owner, name, seconds] for (owner, name), seconds
                    in sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
        "modules": sorted(module_names),
    }


# ------------------------------------------------------------ this process

def newest_xplane(root: str):
    found = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def executed(ctx) -> list:
    """The window's requests that ran on the engine (not result-cache
    hits) and whose query info the server still had."""
    return [r for r in ctx["requests"]
            if (r.get("info") or {}).get("stats")
            and not r["info"]["stats"]["result_cache_hits"]]


def executed_in_slice(ctx) -> float:
    """Executed queries of the traced slice, a query partly inside it
    counted by the share of its time that is (as query_hbm_roofline)."""
    lo, hi = ctx["slice"]
    n = 0.0
    for r in executed(ctx):
        overlap = min(r["t_done"], hi) - max(r["t_send"], lo)
        if overlap > 0:
            n += overlap / (r["t_done"] - r["t_send"])
    return n


def table(ctx):
    """The reduced table of the xplane this process wrote, once per run;
    None where there is nothing to read: no traced slice, no device plane
    (a CPU rehearsal), or a program without request spans AND names."""
    if "_trace_programs" not in ctx:
        out = None
        root = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".bench_out")
        path = newest_xplane(root)
        if ctx.get("trace") and ctx.get("slice") and path:
            spans = [r["info"]["stats"].get("spans") or []
                     for r in executed(ctx)]
            out = reduce(path, spans, ctx["slice"][0], ctx["chips"])
            if out:     # the whole table, for PERF.md: run.py prints none
                with open(os.path.join(root, "trace_programs.json"),
                          "w") as f:
                    json.dump(dict(out, executed_in_slice=executed_in_slice(
                        ctx)), f, indent=1)
        ctx["_trace_programs"] = out
    return ctx["_trace_programs"]


def family_ms_per_query(ctx, family: str):
    t = table(ctx)
    n = executed_in_slice(ctx) if t else 0.0
    if not t or n <= 0 or not any(f in t["by_family"] for f in FAMILIES):
        return None         # a program without names has no families
    return 1e3 * t["by_family"].get(family, 0.0) / n
