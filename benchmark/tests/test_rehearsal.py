"""Every phase of a chip run, rehearsed at `tiny` on the CPU: the same
run.py, load generator, reference and comparison, in a temporary copy to
which the cells were added by new files alone (rehearsal.py)."""

import json
import os
import subprocess
import sys

import pytest

import rehearsal

# the contract's keys, and last the numbers compared beside their limits
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("bench")))


def _bench(copy):
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        return json.load(f)


def _names(entries, cell):
    return {m["name"] for m in entries
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", rehearsal.CELLS)
def test_cell_end_to_end_at_tiny(copy, cell):
    """A 2-second window: every answer equals the reference, the last
    line has exactly the contract's keys and the cell's end-to-end
    metrics, none of them 0, and nothing compiled inside the window."""
    proc, last = rehearsal.drive(copy, cell, 2147483659, 2, 0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last is not None and list(last) == RESULT_KEYS
    assert last["checks"] == {
        "answers_mismatched": {"value": 0, "limit": 0},
        "requests_failed": {"value": 0, "limit": 0}}
    assert proc.stderr.strip().splitlines()[-2:] == [
        "compared answers_mismatched 0 limit 0",
        "compared requests_failed 0 limit 0"]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 2
    assert set(last["metrics"]) == _names(_bench(copy)["end_to_end"], cell)
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    phases = {line["phase"]: line for line in map(
        json.loads, proc.stdout.strip().splitlines()[:-1])}
    assert phases["window"]["compiles_in_window"] == 0
    assert phases["window"]["infos_missing"] == 0
    assert phases["verify"]["answers_checked"] >= 2
    assert "late_max_ms" in phases["window"]["generator"]
    chips = next(c["chips"] for c in _bench(copy)["workloads"]
                 if c["name"] == cell)
    assert phases["chips"]["ids"] == list(range(chips))
    assert len(phases["chips"]["peak_bytes"]) == chips
    if cell == "tiny-dashboard":
        shapes = phases["window"]["by_shape"]
        assert set(shapes) == {"q6", "q1", "q3"}
        assert any(s["hit_share"] > 0 for s in shapes.values())
    if cell == "tiny-mesh4-power":
        # the deployment its file names: every query crossed the mesh,
        # in one program per stage (none staged through the host)
        assert set(phases["window"]["by_shape"]) == {"q1", "q3"}
        mesh = phases["window"]["mesh"]
        assert mesh["mesh_devices"] == [4]
        assert mesh["exchanges_staged"] == [0]
        assert min(mesh["exchanges_fused"]) >= 1


@pytest.mark.parametrize("cell", ("tiny-dashboard", "tiny-count"))
def test_traced_run_reports_the_layer_metrics(copy, cell):
    """--trace 1 on the CPU: the profiler runs and the xplane is reduced
    (it has no device plane, so the device's metrics are left out and no
    CPU number appears under their names); the counters' and spans'
    metrics are the cell's own."""
    proc, last = rehearsal.drive(copy, cell, 7, 2, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is True
    want = _names(_bench(copy)["per_layer"], cell)
    device_only = {"device_idle_share", "query_hbm_roofline", "peak_hbm_GB",
                   "idle_in_request_share", "idle_unattributed_share",
                   "device_time_attributed_share",
                   "scan_filter_device_ms_per_q", "aggregate_device_ms_per_q",
                   "join_device_ms_per_q", "sort_device_ms_per_q"}
    assert set(last["metrics"]) == want - device_only
    assert "breakdown" not in last and "busy_s" not in last["device"]
    if cell == "tiny-count":        # the metric added by a file alone
        assert last["metrics"]["requests_seen"]["value"] \
            == last["attempted"]
    else:
        assert 0 < last["metrics"]["result_cache_hit_share"]["value"] < 100


def test_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(rehearsal.BENCH, "run.py"),
         "--workload", "sf10-scan-agg", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=rehearsal.ROOT,
        timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


# the timed path broken underneath: one value altered where the server
# encodes its rows, for q1's answers only
TAMPER = '''
import trino_tpu.server.app as app
_encode = app.protocol.encode_rows
def _tampered(rows, types):
    data = _encode(rows, types)
    if data and len(data[0]) == 10:            # q1's ten columns
        data[0][-1] += 1                       # count_order of one group
    return data
app.protocol.encode_rows = _tampered
'''


def test_a_wrong_answer_comes_out_as_not_correct(copy):
    proc, last = rehearsal.drive(copy, "tiny-scan-agg", 11, 2, 0,
                                 extra=TAMPER)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is False
    verify = [json.loads(line) for line in proc.stdout.splitlines()
              if '"phase": "verify"' in line][0]
    assert verify["answers_mismatched"] > 0
    assert verify["first_mismatch"].startswith("q1")


# the control: the arithmetic one step below what the configuration's
# guarantees need. The engine sets 64-bit mode when it is imported;
# switching it off afterwards is the lower precision a later PR could be
# tempted by (32-bit lanes are what the TPU is fast at).
X64_OFF = '''
import trino_tpu
jax.config.update("jax_enable_x64", False)
'''


@pytest.mark.parametrize("cell", ("tiny-scan-agg", "tiny-join"))
def test_control_32bit_arithmetic_is_not_correct(copy, cell):
    """With 64-bit arithmetic off the run crashes, or ends with
    `correct` false: either way the control has failed, as it must."""
    proc, last = rehearsal.drive(copy, cell, 13, 2, 0, extra=X64_OFF)
    assert proc.returncode != 0 or last is None or last["correct"] is False
