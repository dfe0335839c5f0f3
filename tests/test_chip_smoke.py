"""chip_smoke.py rehearsed on the CPU: the same phase functions the chip
run drives, at `tiny`, and the chip-or-fail contract of `main()`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)    # chip_smoke.py lives at the repo root

import chip_smoke  # noqa: E402


def _phases(capsys):
    return {line["phase"]: line for line in
            map(json.loads, capsys.readouterr().out.splitlines())}


def test_served_phases_at_tiny(capsys):
    """Server up, q6/q1/q3 cold and warm, the literal variant, the EXECUTE
    pair, nodes and metrics — every answer equal to the NumPy reference
    (serve_phases raises on the first difference)."""
    chip_smoke.serve_phases("tiny", "cpu")
    phases = _phases(capsys)
    assert phases["reference_columns"]["lineitem_rows"] == 60050
    assert all(t["resident"] for t in phases["data_load"]["tables"])
    for name in ("q6", "q1", "q3"):
        assert phases[f"{name}_cold"]["equals_reference"]
        assert phases[f"{name}_warm"]["jit_misses"] == 0
    assert phases["q1_cold"]["rows"] == 4 and phases["q3_cold"]["rows"] == 10
    assert phases["q6_other_literals"]["jit_misses"] == 0
    assert phases["execute_2"]["jit_misses"] == 0
    assert all(p.get("spilled_bytes", 0) == 0 and p.get("retries", 0) == 0
               for p in phases.values())
    node = phases["node"]["nodes"][0]
    assert (node["node_id"], node["pool_budget_source"]) == ("cpu-0",
                                                             "default")


def test_reference_notices_a_wrong_answer():
    want = [["A", "10.00", 3]]
    chip_smoke.check("same", [["A", "10.00", 3]], want)
    chip_smoke.check("double", [[1.0 + 1e-12]], [[1.0]])
    for got in ([["A", "10.01", 3]], [["A", "10.00", 3], ["B", "1.00", 1]],
                [[1.0 + 1e-6]]):
        with pytest.raises(AssertionError):
            chip_smoke.check("differs", got,
                             want if len(got[0]) == 3 else [[1.0]])


@pytest.mark.mesh
def test_mesh_phases_at_tiny_on_four_devices(capsys):
    """The --chips 4 path on four virtual CPU devices: rows equal the
    one-device runner, the exchanges stay in-program, shard i's pages
    live on device i."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs the forced multi-device CPU mesh")
    chip_smoke.mesh_phases("tiny", jax.devices()[:4])
    phases = _phases(capsys)
    for name in ("mesh_q1", "mesh_q3", "mesh_join_count"):
        assert phases[name]["mesh_devices"] == 4
        assert phases[name]["exchanges_staged"] == 0
        assert phases[name]["equals_one_device"]
    assert phases["mesh_placement"]["shards_on_own_device"] == 4


def test_main_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
