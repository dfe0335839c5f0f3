"""What the host was doing: the traced slice by executor thread and activity.

`trace_programs.py` says whose device time it is; its `idle_by_span` gives a
device-idle gap to the first of `compile`, `result_fetch`, `planning`,
`dispatch` that ANY request's span covers, and `dispatch` is a remainder.
Since PR 39 the engine names what an executor thread does where it does it
(`obs/stats.activity`): each entry is a `jax.profiler.TraceAnnotation`
`host__<activity>[:<detail>]`, each phase of a request `request__<phase>`,
and the profiler writes both into the xplane's `/host:` plane, one line a
thread, on the device trace's own clock. This module lays them on the
device's intervals directly, in the trace's nanoseconds:

  reduce(path, spans_by_query, t_begin, chips)
                        -> the table below, or None without a device
                           plane or without any `host__*` event (a program
                           from before the activities)
  table(ctx)            -> the same for the xplane this process wrote,
                           cached on `ctx`, kept whole as
                           .bench_out/host_timeline.json

  idle_by_activity   seconds in which none of the cell's chips ran an
                     operation, cut at every event boundary; a piece is
                     shared equally among the threads inside a
                     `request__execution` then, each thread's share going
                     to its innermost open `host__*` activity, else to
                     `interpreter` (generators, `Page` construction, the
                     collector's bookkeeping: computed, never stamped).
                     No thread executing: `planning` if one is inside
                     `request__planning`, else `queued_only` (a `queued`
                     span of `stats.spans` covers the piece), else
                     `no_request`. By thread, not by priority; it sums to
                     `idle_s`
  busy_by_activity   the same for the rest of the slice: what the host
                     did while a chip ran
  clock_skew_ms      median over the slice's `request__execution` events
                     of |start in the xplane - the `execution` span's
                     start in `stats.spans` laid on the trace through
                     `bench_slice_begin`'s stamp|: how far the mapping
                     that `trace_programs.idle_by_span` rests on is off
  threads            thread lines that held a `request__execution`

An activity's detail (which program's call, which site's read) is not
tabulated: the xplane keeps it for whoever opens the `/host:` plane.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics

import trace_programs
import trace_reduce

HOST, REQUEST = "host__", "request__"
INTERPRETER = "interpreter"
# how far a span of `stats.spans`, laid on the trace, may lie from the
# annotation of the same phase and still be taken for it
SKEW_TOLERANCE_NS = 5_000_000


def _slice(planes) -> tuple:
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
    return lo, hi


def thread_events(planes) -> list:
    """One list per host thread line that holds any: [(start_ns, end_ns,
    name)] of its `host__*` and `request__*` events, an outer one before
    the inner ones that start with it."""
    threads = []
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            mine = sorted(
                ((start, start + duration, meta["name"])
                 for meta, start, duration in line["events"]
                 if duration > 0
                 and meta["name"].startswith((HOST, REQUEST))),
                key=lambda e: (e[0], -e[1]))
            if mine:
                threads.append(mine)
    return threads


def add_cut_executions(threads: list, executions: list, lo, hi) -> None:
    """The profiler keeps an annotation only if it began and ended inside
    the session: a query under way when the trace starts or stops leaves
    its finished `host__*` activities on its thread's line and no
    `request__execution` around them. `executions`: [(start_ns, end_ns)]
    of the `execution` spans of `stats.spans`, laid on the trace. Each one
    that meets the slice and has no `request__execution` event beginning
    where it begins is given, cut to the slice, to the thread whose
    uncovered activities it holds most of — else to a line of its own,
    all `interpreter`."""
    explicit = sorted(start for t in threads for start, _, name in t
                      if name == REQUEST + "execution")
    for start, end in sorted(executions):
        if end <= lo or start >= hi:
            continue
        i = bisect.bisect_left(explicit, start)
        if any(abs(start - s) <= SKEW_TOLERANCE_NS
               for s in explicit[max(i - 1, 0):i + 1]):
            continue
        a, b = max(start, lo), min(end, hi)
        best, held = None, 0
        for events in threads:
            inside = 0
            for s, e, name in events:
                if name == REQUEST + "execution" \
                        and min(e, b) - max(s, a) > SKEW_TOLERANCE_NS:
                    inside = -1     # this thread ran another query then
                    break
                if name.startswith(HOST):
                    inside += max(0, min(e, b) - max(s, a))
            if inside > held:
                best, held = events, inside
        if best is None:
            best = []
            threads.append(best)
        best.append((a, b, REQUEST + "execution"))
        best.sort(key=lambda e: (e[0], -e[1]))


def _owner(executing: list, stacks: list, planning: int, queued: int):
    """-> [(activity, share)] of an instant."""
    if executing:
        share = 1.0 / len(executing)
        return [((stacks[thread][-1] if stacks[thread] else INTERPRETER),
                 share) for thread in executing]
    return [("planning" if planning else
             "queued_only" if queued else "no_request", 1.0)]


def reduce(path: str, spans_by_query: list, t_begin: float, chips=(0,)):
    """`spans_by_query`: one list of [name, start, end] (`stats.spans` of
    a query, on time.monotonic()) per executed query; `t_begin`: that
    clock at `bench_slice_begin`; `chips`: the ids of the cell's devices."""
    planes = trace_programs.read_xspace(path)
    lo, hi = _slice(planes)
    if lo is None or hi is None or hi <= lo:
        raise ValueError(f"{path}: slice annotations missing ({lo}, {hi})")
    mine = {trace_reduce.plane_name(chip) for chip in chips}
    intervals, device = [], False
    for plane in planes:
        if plane["name"] not in mine:
            continue
        for line in plane["lines"]:
            if line["name"] != trace_reduce.OPS_LINE:
                continue
            device = True
            for _, start, duration in line["events"]:
                a, b = max(start, lo), min(start + duration, hi)
                if b > a:
                    intervals.append((a, b))
    threads = thread_events(planes)
    if not device or not any(
            name.startswith(HOST) for t in threads for _, _, name in t):
        return None
    gaps = trace_reduce._gaps(intervals, lo, hi)
    to_ns = lambda mono: lo + (mono - t_begin) * 1e9     # noqa: E731
    skew = clock_skew_ms(threads, spans_by_query, to_ns, lo, hi)
    add_cut_executions(threads, [
        (to_ns(start), to_ns(end)) for spans in spans_by_query
        for name, start, end in spans if name == "execution"], lo, hi)

    # one sweep over every boundary: (time, order, kind, thread, name);
    # at one instant ends come before starts
    marks = [(hi, 0, "end", -1, 0)]
    for a, b in gaps:
        marks.append((a, 1, "idle", -1, 1))
        marks.append((b, 0, "idle", -1, -1))
    for spans in spans_by_query:
        for name, start, end in spans:
            if name == "queued" and end > start:
                marks.append((to_ns(start), 1, "queued", -1, 1))
                marks.append((to_ns(end), 0, "queued", -1, -1))
    for thread, events in enumerate(threads):
        for start, end, name in events:
            marks.append((start, 1, "open", thread, name))
            marks.append((end, 0, "close", thread, name))
    marks.sort(key=lambda m: (m[0], m[1]))

    stacks = [[] for _ in threads]
    executing_depth = [0] * len(threads)
    planning = queued = idle = 0
    idle_by, busy_by = {}, {}
    at = lo
    for t, _, kind, thread, what in marks:
        a, b = max(at, lo), min(t, hi)
        if b > a:
            executing = [i for i, d in enumerate(executing_depth) if d]
            by = idle_by if idle > 0 else busy_by
            for activity, share in _owner(executing, stacks, planning,
                                          queued):
                by[activity] = by.get(activity, 0.0) + (b - a) * 1e-9 * share
        at = max(at, t)
        if kind == "idle":
            idle += what
        elif kind == "queued":
            queued += what
        elif kind in ("open", "close"):
            step = 1 if kind == "open" else -1
            if what.startswith(HOST):
                if step > 0:    # `host__<activity>[:<detail>]`
                    stacks[thread].append(
                        what[len(HOST):].partition(":")[0])
                elif stacks[thread]:
                    stacks[thread].pop()
            elif what == REQUEST + "execution":
                executing_depth[thread] += step
            elif what == REQUEST + "planning":
                planning += step

    idle_s = sum(b - a for a, b in gaps) * 1e-9
    if abs(sum(idle_by.values()) - idle_s) > 0.01 * max(idle_s, 1e-9):
        raise ValueError(f"{path}: idle_by_activity sums to "
                         f"{sum(idle_by.values())}, the gaps to {idle_s}")
    return {
        "window_s": (hi - lo) * 1e-9, "idle_s": idle_s,
        "busy_s": (hi - lo) * 1e-9 - idle_s,
        "idle_by_activity": idle_by, "busy_by_activity": busy_by,
        "clock_skew_ms": skew,
        "threads": sum(any(name == REQUEST + "execution"
                           for _, _, name in t) for t in threads),
    }


def clock_skew_ms(threads, spans_by_query, to_ns, lo, hi):
    """Each `execution` span of `stats.spans` that starts inside the
    slice, against the nearest `request__execution` start of the xplane."""
    starts = sorted(start for t in threads for start, _, name in t
                    if name == REQUEST + "execution")
    off = []
    for spans in spans_by_query:
        for name, start, _ in spans:
            at = to_ns(start)
            if name != "execution" or not starts or not lo <= at <= hi:
                continue
            i = bisect.bisect_left(starts, at)
            off.append(min(abs(at - s) for s in starts[max(i - 1, 0):i + 1]))
    return statistics.median(off) * 1e-6 if off else None


# ------------------------------------------------------------ this process

def table(ctx):
    """The reduced table of the xplane this process wrote, once per run;
    None where there is nothing to read: no traced slice, no device plane
    (a CPU rehearsal), or a program without the activities."""
    if "_host_timeline" not in ctx:
        out = None
        root = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".bench_out")
        path = trace_programs.newest_xplane(root)
        if ctx.get("trace") and ctx.get("slice") and path:
            spans = [r["info"]["stats"].get("spans") or []
                     for r in trace_programs.executed(ctx)]
            out = reduce(path, spans, ctx["slice"][0], ctx["chips"])
            if out:
                with open(os.path.join(root, "host_timeline.json"),
                          "w") as f:
                    json.dump(out, f, indent=1)
        ctx["_host_timeline"] = out
    return ctx["_host_timeline"]


def counter_mean(ctx, key: str):
    """Mean of `stats[key]` over the executed queries that carry it."""
    values = [r["info"]["stats"][key] for r in trace_programs.executed(ctx)
              if key in r["info"]["stats"]]
    return sum(values) / len(values) if values else None
