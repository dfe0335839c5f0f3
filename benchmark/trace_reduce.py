"""From a `jax.profiler` xplane file to the numbers the benchmark reports.

The traced slice is what lies between the two host annotations
`bench_slice_begin` and `bench_slice_end` that `run.py` emits; each is
also stamped with `time.monotonic()`, which lays the load generator's
request intervals on the trace's clock. On every device plane
(`/device:TPU:<n>`, n the device's id) the line `XLA Ops` holds one event
per operation that ran there. Only the planes of the cell's chips are
read: on a four-chip host a one-chip cell is not averaged over four.

  busy_s      union of those events' intervals inside the slice, the mean
              over the cell's chips; `busy_s_per_chip` has each
  window_s    length of the slice
  device_ops  the ten operation names with most summed time (the instruction's name
              and opcode as XLA printed them), [[name, seconds], ...], the
              mean over the cell's chips
  idle_gaps   the time inside the slice in which none of the cell's chips
              ran an operation (the gaps of the union of their intervals),
              summed by what the
              load generator had in flight at the middle of each gap:
              `between_requests`, or `in_request:<shapes>`;
              [[name, seconds], ...], the ten largest sums
  idle_in_request_s   all of that idle time but `between_requests`

What the host was doing inside a request (plan, dispatch, encode) needs
spans inside the program: PERF.md lists it for the tracing issue.
"""

from __future__ import annotations

import glob
import os
import re

BEGIN, END = "bench_slice_begin", "bench_slice_end"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no xplane file under {trace_dir}")
    return found[-1]


def short_name(hlo: str) -> str:
    """'%sort.6 = (s32[..]..) sort(...), dimensions=..' -> '%sort.6 sort':
    the instruction's own name and its opcode, as XLA printed them."""
    head, _, rest = hlo.partition(" = ")
    opcode = re.search(r"[\s)}\]]([a-z][\w-]*)\(", " " + rest)
    kind = re.search(r"kind=(\w+)", rest)
    return " ".join(filter(None, (head, opcode and opcode.group(1),
                                  kind and kind.group(1))))


def _anchor(profile, name: str):
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name == name:
                    return event.start_ns
    return None


def _union(intervals: list) -> float:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _gaps(intervals: list, lo: int, hi: int) -> list:
    gaps, at = [], lo
    for a, b in sorted(intervals):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def plane_name(chip: int) -> str:
    return f"/device:TPU:{chip}"


def reduce_xplane(path: str, requests: list, t_begin: float,
                  chips=(0,)) -> dict:
    """`requests`: [{"shape", "t_send", "t_done"}] on `time.monotonic()`;
    `t_begin`: that clock's reading when `bench_slice_begin` was emitted;
    `chips`: the ids of the cell's devices — one of them that ran nothing
    counts as a chip that was idle. Without an `XLA Ops` line on any of
    their planes (a CPU rehearsal) only `planes` comes back: every
    plane's lines with their event counts."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    lo, hi = _anchor(profile, BEGIN), _anchor(profile, END)
    if lo is None or hi is None or hi <= lo:
        raise ValueError(f"{path}: slice annotations missing ({lo}, {hi})")
    by_plane, by_name, planes = {}, {}, {}
    mine = {plane_name(chip) for chip in chips}
    for plane in profile.planes:
        planes[plane.name] = {line.name: sum(1 for _ in line.events)
                              for line in plane.lines}
        if plane.name not in mine:
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            spans = by_plane.setdefault(plane.name, [])
            for event in line.events:
                a = max(int(event.start_ns), lo)
                b = min(int(event.start_ns + event.duration_ns), hi)
                if b > a:
                    spans.append((a, b))
                    name = short_name(event.name)
                    by_name[name] = by_name.get(name, 0) + b - a
    if not by_plane:
        return {"planes": planes}
    per_device = [by_plane.get(plane_name(chip), []) for chip in chips]
    # ns on the trace's clock -> seconds on time.monotonic()
    to_mono = lambda ns: t_begin + (ns - lo) * 1e-9  # noqa: E731
    idle = {}
    for a, b in _gaps([s for spans in per_device for s in spans], lo, hi):
        mid = to_mono((a + b) / 2)
        shapes = sorted({r["shape"] for r in requests
                         if r["t_send"] <= mid <= r["t_done"]})
        name = "in_request:" + "+".join(shapes) if shapes \
            else "between_requests"
        idle[name] = idle.get(name, 0) + b - a
    n = len(per_device)
    top = lambda d: [[k, v * 1e-9] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    busy = [_union(s) for s in per_device]
    return {
        "busy_s": sum(busy) * 1e-9 / n,
        "busy_s_per_chip": [b * 1e-9 for b in busy],
        "window_s": (hi - lo) * 1e-9,
        "devices_traced": n, "planes": planes,
        "device_ops": [[k, v / n] for k, v in top(by_name)],
        "idle_gaps": top(idle),
        "idle_in_request_s": sum(
            v for k, v in idle.items() if k != "between_requests") * 1e-9,
    }
