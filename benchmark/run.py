"""One run of one benchmark cell, in a new process, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (a deployment), its traffic mix and its
metrics are found by name from BENCHMARK.json and the files beside this
one; nothing here knows a cell. The phases:

  set-up   TPU or exit non-zero (no CPU fallback); persistent compile
           cache; the deployment the configuration file names — its
           `runner` ("local", the default, or "mesh") over the cell's
           chips, `jax.devices()[:chips]` — behind one `TrinoServer` on a
           loopback port with the configuration's columns warmed on the
           device; the load generator (`loadgen.py`, a child process that
           never imports JAX) PREPAREs, warms each shape once and prefills
           the result cache. All of that is `setup_s`.
  window   the child drives `/v1/statement` for --seconds; every
           end-to-end number is taken on the client's side. With
           --trace 1 this process (it holds the chip) profiles a slice.
  after    query infos over `GET /v1/query/<id>`; server stopped; then
           the NumPy reference
           (`reference.py`, worker processes) answers the window's
           distinct queries and every served answer is compared.

Every line but the last is a phase's JSON for the reader; the last line
is the result object of the benchmark's contract.
"""

from __future__ import annotations

import time

T0 = time.monotonic()       # as near to process start as Python allows

import argparse             # noqa: E402
import json                 # noqa: E402
import math                 # noqa: E402
import os                   # noqa: E402
import random               # noqa: E402
import shutil               # noqa: E402
import statistics           # noqa: E402
import subprocess           # noqa: E402
import sys                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import loadgen              # noqa: E402
import reference            # noqa: E402
import tpch_columns         # noqa: E402
import trace_programs       # noqa: E402
import trace_reduce         # noqa: E402
import traffic_gen          # noqa: E402


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def metrics_of(entries: list, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def require_devices(chips: int):
    """Every device JAX reports, or a non-zero exit and no result line;
    the cell's chips are the first `chips` of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"run.py: needs a TPU, JAX found "
                         f"{devices[0].platform!r} — not run")
    if len(devices) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chip(s), JAX "
                         f"found {len(devices)} — not run")
    return devices


def peak_bytes(chips) -> list:
    """Peak bytes in use on each of the cell's chips (None on the CPU
    backend): a sharded table that lands on one chip shows here."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in chips]


# ----------------------------------------------------------------- set-up

def make_runner(config: dict, chips):
    """The query runner the configuration names, over the cell's chips."""
    kind = config.get("runner", "local")
    if kind == "local":
        from trino_tpu.exec import LocalQueryRunner
        return LocalQueryRunner.tpch(config["schema"])
    if kind == "mesh":
        from trino_tpu.exec.distributed import DistributedQueryRunner
        return DistributedQueryRunner.tpch(config["schema"], devices=chips)
    raise SystemExit(f"run.py: configuration {config['name']!r} names the "
                     f"runner {kind!r}; there are 'local' and 'mesh'")


def start_server(config: dict, chips):
    from trino_tpu.server import TrinoServer
    manifest = {"tables": [
        {"table": f"{config['catalog']}.{config['schema']}.{t}",
         "columns": names} for t, names in config["columns"].items()]}
    t0 = time.monotonic()
    server = TrinoServer(make_runner(config, chips),
                         warmup_manifest=manifest,
                         **config["server"]).start()
    failed = [e for e in server.warmup_report if "error" in e]
    if failed:
        server.stop()
        raise SystemExit(f"run.py: table warm-up failed: {failed}")
    emit("data_load", seconds=time.monotonic() - t0,
         tables=server.warmup_report)
    return server


def start_generator(plan: dict) -> subprocess.Popen:
    """The child, through its set-up statements, waiting for `go`."""
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    child.stdin.write(json.dumps(plan) + "\n")
    child.stdin.flush()
    line = child.stdout.readline()
    ready = json.loads(line) if line.strip() else {}
    failed = [s for s in ready.get("setup", []) if s["error"]]
    if not ready.get("ready") or failed:
        child.kill()
        child.wait()
        raise SystemExit(f"run.py: the load generator's set-up failed: "
                         f"{failed or line!r}")
    emit("generator_setup", statements=ready["setup"])
    return child


# ----------------------------------------------------------------- window

def profile_slice(trace_dir: str, t_go: float, seconds: float,
                  slice_s: float):
    """Profile [start, start + slice) of the window from this process;
    -> (monotonic at the begin annotation, at the end annotation)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the Python tracer slows the host
    options.host_tracer_level = 1       # TraceAnnotations only
    start = t_go + min(2.0, 0.1 * seconds)
    time.sleep(max(0.0, start - time.monotonic()))
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        t_begin = time.monotonic()
        with jax.profiler.TraceAnnotation(trace_reduce.BEGIN):
            pass
        time.sleep(min(slice_s, 0.8 * seconds))
        t_end = time.monotonic()
        with jax.profiler.TraceAnnotation(trace_reduce.END):
            pass
    finally:
        jax.profiler.stop_trace()
    return t_begin, t_end


def query_infos(conn: loadgen.Conn, requests: list) -> int:
    """Attach GET /v1/query/<id> to each request; -> how many the
    server no longer had (its history ring holds 512)."""
    missing = 0
    for r in requests:
        r["info"] = None
        if r["qid"]:
            try:
                info = conn.get(f"/v1/query/{r['qid']}")
            except (OSError, ValueError):
                info = None
            r["info"] = info if info and info.get("stats") else None
        missing += r["info"] is None
    return missing


# ----------------------------------------------------------------- verify

def verify(requests: list, config: dict, traffic: dict, seed: int) -> dict:
    """Compare every served answer of a seeded sample of the window's
    distinct (shape, parameters) with the reference. Limits are 0: the
    configuration guarantees exact answers."""
    ok = [r for r in requests if not r["error"]]
    for r in ok:
        r["key"] = (r["shape"], json.dumps(r["params"], sort_keys=True))
    distinct = sorted({r["key"] for r in ok})
    sample = distinct
    if len(distinct) > traffic["verify_max_distinct"]:
        sample = sorted(random.Random(f"{seed}:verify").sample(
            distinct, traffic["verify_max_distinct"]))
    t0 = time.monotonic()
    keys = [(shape, json.loads(params)) for shape, params in sample]
    want = dict(zip(sample, reference.compute(
        config["scale_factor"], keys, min(os.cpu_count() or 1, 12))))
    checked = mismatched = 0
    first = None
    for r in ok:
        r["verified"] = None
        if r["key"] in want:
            diff = reference.compare(r["rows"], want[r["key"]])
            r["verified"] = not diff
            checked += 1
            if diff:
                mismatched += 1
                first = first or f"{r['shape']} {r['params']}: {diff}"
    failed = len(requests) - len(ok)
    out = {"answers_checked": checked, "answers_mismatched": mismatched,
           "limit_answers_mismatched": 0, "requests_failed": failed,
           "limit_requests_failed": 0, "distinct_in_window": len(distinct),
           "distinct_checked": len(sample),
           "reference_s": time.monotonic() - t0, "first_mismatch": first,
           "first_failure": next((r["error"] for r in requests
                                  if r["error"]), None)}
    emit("verify", **out)
    out["correct"] = checked > 0 and mismatched == 0 and failed == 0
    return out


def compared(checks: dict) -> dict:
    """Each number `correct` was decided on, beside its limit."""
    return {name: {"value": checks[name], "limit": checks[f"limit_{name}"]}
            for name in ("answers_mismatched", "requests_failed")}


# ---------------------------------------------------------------- metrics

def end_to_end(requests, t_go, seconds, traffic, setup_s) -> dict:
    """The client's side of the served path. A failed request counts as
    the window's length; a wrong answer is not a completed query."""
    for r in requests:
        r["latency_s"] = seconds if r["error"] else r["t_done"] - r["t_due"]
    good = [r for r in requests
            if not r["error"] and r.get("verified") is not False]
    out = {"setup_s": setup_s}
    if good and traffic["throughput_over"] == "last_completion":
        # requests under way at the deadline finish, and their time
        # counts: 1 / mean latency of a stream, not cut by the window
        out["throughput_qps"] = len(good) / (
            max(r["t_done"] for r in requests) - t_go)
    elif good:
        out["throughput_qps"] = sum(
            r["t_done"] <= t_go + seconds for r in good) / seconds
    by_shape = {}
    for r in requests:
        by_shape.setdefault(r["shape"], []).append(r["latency_s"])
    if by_shape:
        out["latency_geomean_ms"] = 1e3 * math.exp(statistics.fmean(
            math.log(statistics.median(v)) for v in by_shape.values()))
    return out


def window_line(requests: list, generator: dict, missing: int) -> None:
    by_shape = {}
    for r in requests:
        s = by_shape.setdefault(r["shape"], {"n": 0, "hits": 0, "lat": []})
        s["n"] += 1
        s["lat"].append(r["latency_s"])
        s["hits"] += bool(r["info"]
                          and r["info"]["stats"]["result_cache_hits"])
    ran = [r["info"]["stats"]
           for r in trace_programs.executed({"requests": requests})]
    emit("window", requests=len(requests), infos_missing=missing,
         generator=generator,
         # how the executed queries crossed the deployment's chips: the
         # distinct values of each counter (a local runner reads 0)
         mesh={k: sorted({st.get(k, 0) for st in ran}) for k in (
             "mesh_devices", "exchanges_fused", "exchanges_staged")},
         polls_per_request=sum(r["polls"] for r in requests) / len(requests),
         compiles_in_window=sum(
             r["info"]["stats"]["jit_misses"] for r in requests
             if r["info"]),
         by_shape={k: {"n": s["n"], "hit_share": s["hits"] / s["n"],
                       "median_ms": 1e3 * statistics.median(s["lat"]),
                       "max_ms": 1e3 * max(s["lat"])}
                   for k, s in by_shape.items()})


# ------------------------------------------------------------------- cell

def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, devices) -> dict:
    config_entry = find(bench["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    if config["chips"] != cell["chips"]:
        raise SystemExit(f"run.py: cell {cell['name']!r} takes "
                         f"{cell['chips']} chip(s), its configuration "
                         f"{config['name']!r} is laid out on "
                         f"{config['chips']} — not run")
    chips = devices[:cell["chips"]]
    traffic = traffic_gen.load_traffic(cell["traffic"])
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    kind = devices[0].device_kind
    if devices[0].platform == "tpu" and kind not in peaks:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r}")

    import trino_tpu
    trino_tpu.enable_persistent_cache()
    fingerprint = tpch_columns.fingerprint(config["scale_factor"])
    if fingerprint != config["data_fingerprint"]:
        raise SystemExit(f"run.py: the reference's data changed: "
                         f"fingerprint {fingerprint}, configuration says "
                         f"{config['data_fingerprint']}")

    plan = traffic_gen.make_plan(traffic, seed, seconds)
    server = start_server(config, chips)
    child = None
    try:
        plan.update(host="127.0.0.1", port=server.port)
        child = start_generator(plan)
        setup_s = time.monotonic() - T0
        t_go = time.monotonic()
        child.stdin.write("go\n")
        child.stdin.flush()
        trace_dir = os.path.join(ROOT, ".bench_out", f"trace-{cell['name']}")
        slice_ = profile_slice(trace_dir, t_go, seconds,
                               traffic["trace_slice_s"]) if trace else None
        line = child.stdout.readline()
        if child.wait() != 0 or not line.strip():
            raise SystemExit(f"run.py: the load generator exited "
                             f"{child.returncode} with no results")
        window = json.loads(line)
        requests = sorted(window["requests"], key=lambda r: r["t_send"])
        t_go = window["t_go"]
        chip_peaks = peak_bytes(chips)
        conn = loadgen.Conn("127.0.0.1", server.port, "bench-after")
        try:
            missing = query_infos(conn, requests)
        finally:
            conn.close()
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        server.stop()

    checks = verify(requests, config, traffic, seed)
    measured = end_to_end(requests, t_go, seconds, traffic, setup_s)
    window_line(requests, window["generator"], missing)
    memory_peak = max(chip_peaks, key=lambda p: p or 0)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": checks["correct"], "attempted": len(requests),
              "failed": checks["requests_failed"], "metrics": {},
              "device": device}
    per_chip = {"ids": [d.id for d in chips], "peak_bytes": chip_peaks}
    if not trace:
        wanted = metrics_of(bench["end_to_end"], cell["name"])
        values = measured
    else:
        emit("end_to_end_while_traced", **measured)
        reduced = trace_reduce.reduce_xplane(
            trace_reduce.find_xplane(trace_dir), requests, slice_[0],
            per_chip["ids"])
        emit("trace", **reduced)
        if "busy_s" not in reduced:
            reduced = None          # no device plane: a CPU rehearsal
        ctx = {"requests": requests, "trace": reduced, "slice": slice_,
               "config": config, "peaks": peaks.get(kind),
               "chips": per_chip["ids"],
               "memory_peak_bytes": memory_peak, "seconds": seconds,
               "shapes": {e["shape"]: reference.load_by_path(
                   "queries", e["shape"]) for e in traffic["shapes"]}}
        wanted = metrics_of(bench["per_layer"], cell["name"])
        values = {m["name"]: reference.load_by_path(
            "layer_metrics", m["name"]).read(ctx) for m in wanted}
        if reduced:
            per_chip["busy_s"] = reduced["busy_s_per_chip"]
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            result["breakdown"] = {
                # "<program>/<scope> <instruction>": which operator XLA's
                # `%fusion.3` is (the plain names stay in the trace line)
                "device_ops": [[f"{owner} {op}", s] for owner, op, s
                               in trace_programs.table(ctx)["top_ops"]],
                "idle_gaps": reduced["idle_gaps"]}
    emit("chips", **per_chip)
    for m in wanted:
        if values.get(m["name"]) is not None:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = compared(checks)     # last, as the contract has it
    for name, c in result["checks"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find(bench["workloads"], args.workload, "workload")
    devices = require_devices(cell["chips"])
    result = run_cell(bench, cell, args.seed, args.seconds,
                      bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
