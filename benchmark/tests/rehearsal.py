"""A temporary copy of the benchmark with `tiny` cells, for CPU rehearsals.

`make_copy` twins every configuration and cell that BENCHMARK.json holds
at `tiny`, whatever they are, and adds a cell, a deployment, a query shape,
a traffic mix and a per-layer metric — and a four-device mesh deployment
with its own traffic and cell — by writing NEW files and APPENDING entries
to BENCHMARK.json: it edits no file that is there, which is what a later
PR is held to. `drive` runs one cell of the copy in a new process through
run.py's own `main`, with only the look for a chip stepped over.
"""

import json
import os
import re
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def twin(name: str) -> str:
    """'sf10-scan-agg' -> 'tiny-scan-agg', 'tpch-sf30-4chip' ->
    'tpch-tiny-4chip'; a name without a scale factor gets 'tiny-' in
    front."""
    out = re.sub(r"(^|-)sf\d+(?=-|$)", r"\1tiny", name, count=1)
    return out if out != name else f"tiny-{name}"


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    # the cells of every copy: the twins, then the two added by files
    # (once BENCHMARK.json has a mesh cell of that name, its twin is it)
    CELLS = tuple(dict.fromkeys(
        [twin(cell["name"]) for cell in json.load(_f)["workloads"]]
        + ["tiny-count", "tiny-mesh4-power"]))

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
DRIVER = '''import os
import sys
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, {bench!r})
import jax
import run
run.require_devices = lambda chips: jax.devices()
{extra}
sys.exit(run.main(sys.argv[1:]))
'''


def _write(path: str, data) -> None:
    with open(path, "x") as f:      # "x": a file that is there stays
        f.write(data) if isinstance(data, str) else json.dump(data, f)


def at_scale(config: dict, name: str, schema: str, **changes) -> dict:
    """A deployment's file at another scale factor: its data restated."""
    import tpch_columns
    sf = tpch_columns.SCALE_FACTORS[schema]
    return {**config, "name": name, "schema": schema, "scale_factor": sf,
            "data_fingerprint": tpch_columns.fingerprint(sf),
            "rows": tpch_columns.row_counts(sf), **changes}


MESH_TRAFFIC = {
    "why": "one closed-loop client, q1 then q3 across the mesh; every "
           "parameter is drawn once per run because the program compiles "
           "a mesh program per literal (ROADMAP M2): a parameter per "
           "request would compile inside the window",
    "loop": "closed", "clients": 1, "queue": "per_client",
    "statement": "plain",
    "session": {"join_distribution_type": "PARTITIONED",
                "result_cache_enabled": "false"},
    "order": "sequence",
    "shapes": [{"shape": "q1", "weight": 1, "per_run": ["delta"]},
               {"shape": "q3", "weight": 1, "per_run": ["segment", "date"]}],
    "law": {"kind": "uniform"}, "prefill_ranks": 0,
    "requests_per_client": 2000, "throughput_over": "last_completion",
    "verify_max_distinct": 16, "trace_slice_s": 10}

# the metrics with a list of cells that a q1 + q3 cell has something for
MESH_METRICS = (
    "latency_geomean_ms", "query_hbm_roofline", "host_staging_mb_per_q",
    "device_time_attributed_share", "idle_unattributed_share",
    "scan_filter_device_ms_per_q", "aggregate_device_ms_per_q",
    "join_device_ms_per_q", "sort_device_ms_per_q")


def make_copy(tmp: str, mesh_schema: str = "tiny") -> str:
    """-> `tmp`, holding BENCHMARK.json and benchmark/. `mesh_schema`:
    the scale of the added mesh deployment (`sf1` for the rehearsal on a
    four-chip host, `rehearse_mesh4.py`)."""
    shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench_dir = os.path.join(tmp, "benchmark")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    # the twins: every configuration at `tiny`, every cell on its twin
    twins = {}
    for entry in list(bench["configs"]):
        name = twins[entry["name"]] = twin(entry["name"])
        if any(c["name"] == name for c in bench["configs"]):
            continue                        # sf1 and sf10 of one layout
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = at_scale(json.load(f), name, "tiny")
        _write(os.path.join(bench_dir, "configs", f"{name}.json"), config)
        bench["configs"].append({
            "name": name, "source": "rehearsal", "reduced": [],
            "file": f"benchmark/configs/{name}.json", "why": "rehearsal"})
    cells = {}
    for cell in list(bench["workloads"]):
        cells[cell["name"]] = twin(cell["name"])
        bench["workloads"].append({**cell, "name": cells[cell["name"]],
                                   "config": twins[cell["config"]]})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] += [cells[n] for n in metric["workloads"]]

    def listed(cell: str, names) -> None:
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if metric["name"] in names:
                metric["workloads"].append(cell)

    # a shape, a traffic mix, a metric and a cell of the first
    # configuration's twin (one chip, the local runner)
    first = bench["configs"][0]
    with open(os.path.join(ROOT, first["file"])) as f:
        one_chip = json.load(f)
    with open(os.path.join(BENCH, "traffic", "adhoc-q3.json")) as f:
        traffic = json.load(f)
    traffic["shapes"] = [{"shape": "ocount", "weight": 1, "per_run": []}]
    _write(os.path.join(bench_dir, "traffic", "adhoc-count.json"), traffic)
    _write(os.path.join(bench_dir, "queries", "ocount.py"), COUNT_SHAPE)
    _write(os.path.join(bench_dir, "layer_metrics", "requests_seen.py"),
           COUNT_METRIC)
    bench["workloads"].append({
        "name": "tiny-count", "config": twins[first["name"]],
        "traffic": "adhoc-count", "chips": 1, "why": "rehearsal"})
    bench["per_layer"].append({
        "name": "requests_seen", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "server",
        "moves": "throughput_qps", "workloads": ["tiny-count"]})
    listed("tiny-count", ("latency_geomean_ms",))

    # a deployment of another kind: the mesh runner over four devices
    mesh_cell = f"{mesh_schema}-mesh4-power"
    if mesh_cell not in cells.values():
        mesh = at_scale(one_chip, f"tpch-{mesh_schema}-mesh4", mesh_schema,
                        runner="mesh", chips=4, layout="one TrinoServer "
                        "over one DistributedQueryRunner on a mesh of four "
                        "devices")
        _write(os.path.join(bench_dir, "configs", f"{mesh['name']}.json"),
               mesh)
        _write(os.path.join(bench_dir, "traffic", "power-q1-q3-mesh.json"),
               MESH_TRAFFIC)
        bench["configs"].append({
            "name": mesh["name"], "source": "rehearsal", "reduced": [],
            "file": f"benchmark/configs/{mesh['name']}.json",
            "why": "rehearsal"})
        bench["workloads"].append({
            "name": mesh_cell, "config": mesh["name"],
            "traffic": "power-q1-q3-mesh", "chips": 4, "why": "rehearsal"})
        listed(mesh_cell, MESH_METRICS)
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
