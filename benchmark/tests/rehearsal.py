"""A temporary copy of the benchmark with `tiny` cells, for CPU rehearsals.

`make_copy` adds a cell, a deployment, a query shape, a traffic mix and a
per-layer metric to the copy by writing NEW files and APPENDING entries to
BENCHMARK.json — it edits no file that is there, which is what a later PR
is held to. `drive` runs one cell of the copy in a new process through
run.py's own `main`, with only the look for a chip stepped over.
"""

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CELLS = ("tiny-scan-agg", "tiny-join", "tiny-dashboard", "tiny-count")

COUNT_SHAPE = '''"""A shape added by files alone: orders before a date."""
from wire import days

SQL = "SELECT count(*) FROM orders WHERE o_orderdate < DATE '{date}'"
PREPARED = "SELECT count(*) FROM orders WHERE o_orderdate < ?"
USING = "DATE '{date}'"
DOMAIN = {"date": [f"199{y}-0{m}-01" for y in range(3, 8) for m in (1, 7)]}
COLUMNS = {"orders": ["o_orderdate"]}


def needed_bytes(row_counts, column_bytes):
    return row_counts["orders"] * column_bytes["o_orderdate"]


def partial(c, p, customer):
    return int((c["o_orderdate"] < days(p["date"])).sum())


def merge(partials, p):
    return [[sum(partials)]]
'''

COUNT_METRIC = '''"""A per-layer metric added by files alone."""


def read(ctx):
    return float(len(ctx["requests"]))
'''

# a driver that steps over the look for a chip and nothing else; the
# tests append lines to it that break the program underneath
DRIVER = '''import sys
sys.path.insert(0, {bench!r})
import jax
import run
run.require_devices = lambda chips: jax.devices()[:chips]
{extra}
sys.exit(run.main(sys.argv[1:]))
'''


def make_copy(tmp: str) -> str:
    shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench_dir = os.path.join(tmp, "benchmark")
    with open(os.path.join(BENCH, "configs", "tpch-sf1-1chip.json")) as f:
        config = json.load(f)
    config.update(name="tpch-tiny", schema="tiny", scale_factor=0.01,
                  data_fingerprint="6b527f51",
                  rows={"lineitem": 60050, "orders": 15000,
                        "customer": 1500})
    with open(os.path.join(bench_dir, "configs", "tpch-tiny.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(BENCH, "traffic", "adhoc-q3.json")) as f:
        traffic = json.load(f)
    traffic["shapes"] = [{"shape": "ocount", "weight": 1, "per_run": []}]
    with open(os.path.join(bench_dir, "traffic", "adhoc-count.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench_dir, "queries", "ocount.py"), "w") as f:
        f.write(COUNT_SHAPE)
    with open(os.path.join(bench_dir, "layer_metrics", "requests_seen.py"),
              "w") as f:
        f.write(COUNT_METRIC)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tpch-tiny", "source": "rehearsal",
        "file": "benchmark/configs/tpch-tiny.json", "reduced": [],
        "why": "rehearsal"})
    twins = {"sf10-scan-agg": "tiny-scan-agg", "sf10-join": "tiny-join",
             "sf1-dashboard": "tiny-dashboard"}
    for cell in list(bench["workloads"]):
        bench["workloads"].append({**cell, "name": twins[cell["name"]],
                                   "config": "tpch-tiny"})
    bench["workloads"].append({
        "name": "tiny-count", "config": "tpch-tiny",
        "traffic": "adhoc-count", "chips": 1, "why": "rehearsal"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] += [twins[n] for n in metric["workloads"]]
    bench["end_to_end"][1]["workloads"].append("tiny-count")
    bench["per_layer"].append({
        "name": "requests_seen", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "server",
        "moves": "throughput_qps", "workloads": ["tiny-count"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return tmp


def drive(copy: str, cell: str, seed: int, seconds: float, trace: int,
          extra: str = "", timeout: float = 600):
    """-> (CompletedProcess, the last stdout line parsed or None)."""
    driver = os.path.join(copy, "drive.py")
    with open(driver, "w") as f:
        f.write(DRIVER.format(bench=os.path.join(copy, "benchmark"),
                              extra=extra))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable, driver, "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, env=env, cwd=copy, timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1]) if lines else None
    except ValueError:
        last = None
    if not (isinstance(last, dict) and "correct" in last):
        last = None
    return proc, last
