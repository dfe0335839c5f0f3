"""The plain reference: NumPy over the benchmark's own columns.

`compute()` answers a list of (shape, parameters) over a whole scale
factor. It runs after the window has closed, in worker processes that
import NumPy only (never JAX, so they cannot touch the chip): each takes
a contiguous range of orders with all their lineitems, generates it from
`tpch_columns`, and returns every query's partial answer for that range;
the shape's own `merge` joins the partials (which travel as JSON). `compare()` is the rule that
decides whether a served answer equals the reference's.

Run as a script it is one such worker: a job as JSON on stdin, the
partials as JSON on stdout.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHUNK_ORDERS = 250_000


def load_by_path(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module, found by name alone."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _worker(job: dict) -> list:
    import tpch_columns as C
    shapes = {s: load_by_path("queries", s)
              for s in {shape for shape, _ in job["keys"]}}
    customer = C.customer(job["sf"])
    partials = [[] for _ in job["keys"]]
    row0 = C.lineitem_rows_before(job["sf"], job["o_first"])
    for first in range(job["o_first"], job["o_last"], CHUNK_ORDERS):
        chunk = C.orders_chunk(job["sf"], first,
                               min(first + CHUNK_ORDERS, job["o_last"]), row0)
        row0 += len(chunk["l_orderkey"])
        for out, (shape, params) in zip(partials, job["keys"]):
            out.append(shapes[shape].partial(chunk, params, customer))
    return partials


def compute(sf: float, keys: list, workers: int) -> list:
    """Reference rows for each (shape, params) of `keys`, in order."""
    import tpch_columns as C
    if not keys:
        return []
    n = C.order_count(sf)
    workers = max(1, min(workers, -(-n // CHUNK_ORDERS)))
    bounds = [n * i // workers for i in range(workers + 1)]
    env = dict(os.environ, OMP_NUM_THREADS="1", OPENBLAS_NUM_THREADS="1")
    procs = []
    try:
        for lo, hi in zip(bounds, bounds[1:]):
            p = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)], env=env,
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
            p.stdin.write(json.dumps(
                {"sf": sf, "keys": keys, "o_first": lo, "o_last": hi}))
            p.stdin.close()
            procs.append(p)
        outs = []
        for p in procs:
            out = p.stdout.read()
            if p.wait() != 0:
                raise RuntimeError(f"reference worker exited {p.returncode}")
            outs.append(json.loads(out))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()
    rows = []
    for i, (shape, params) in enumerate(keys):
        parts = [part for out in outs for part in out[i]]
        rows.append(load_by_path("queries", shape).merge(parts, params))
    return rows


def compare(got: list, want: list) -> str:
    """'' when the served rows equal the reference's row for row —
    decimals, keys, dates and counts exactly, doubles to 1e-9 relative —
    else the first difference."""
    if len(got) != len(want):
        return f"{len(got)} rows, reference has {len(want)}"
    for i, (g, w) in enumerate(zip(got, want)):
        if len(g) != len(w):
            return f"row {i}: {g} vs reference {w}"
        for a, b in zip(g, w):
            if isinstance(b, float):
                same = isinstance(a, (int, float)) and \
                    abs(a - b) <= 1e-9 * max(abs(b), 1e-300)
            else:
                same = type(a) is type(b) and a == b
            if not same:
                return f"row {i}: {g} vs reference {w}"
    return ""


if __name__ == "__main__":
    sys.path.insert(0, HERE)
    json.dump(_worker(json.load(sys.stdin)), sys.stdout)
