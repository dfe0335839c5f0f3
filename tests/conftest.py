"""Test configuration: run on CPU with 8 virtual devices.

Multi-chip hardware is not available in CI; sharding tests exercise a virtual
8-device CPU mesh (mirrors how the driver dry-runs dryrun_multichip). Must be
set before jax initializes — conftest is imported before any test module.

The `mesh` marker (pytest.ini) tags the multi-chip sharded-execution suite
(tests/test_mesh_queries.py): under this conftest it runs inline on the
forced 8-device mesh; collected into a process whose backend came up with
fewer devices, the module re-runs itself in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 — either way tier-1
exercises the sharded path without a TPU.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: tests never touch an accelerator
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_enable_x64", True)
# belt and braces with the env var above: the config knob wins over
# whatever the environment or an installed backend plug-in says
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite compiles hundreds of fused query
# kernels; caching them on disk makes re-runs near-instant and keeps
# cumulative in-process LLVM compilation (which has crashed the CPU backend
# under the full 22-query distributed sweep) bounded.
import trino_tpu

trino_tpu.enable_persistent_cache()

import pytest


@pytest.fixture(autouse=True)
def node_pool_leak_gate():
    """Leak gate: after EVERY engine test the node memory pool must read
    zero reserved bytes — a nonzero pool means some query's ledger closed
    dirty or never closed (the reservation-leak class of bug this round's
    resource-governance layer exists to catch). Server tests finish
    queries on background executor threads, so give stragglers a short
    grace window before failing."""
    yield
    import time

    from trino_tpu.exec.memory import NODE_POOL
    deadline = time.monotonic() + 5.0
    while NODE_POOL.reserved != 0 and time.monotonic() < deadline:
        time.sleep(0.02)
    leaked, culprits = NODE_POOL.reserved, list(NODE_POOL._contexts)
    if leaked:
        # reset so exactly ONE test reports the leak — without this,
        # every subsequent test inherits the nonzero pool (plus the 5s
        # grace wait) and the real culprit drowns in cascade failures
        with NODE_POOL._cond:
            NODE_POOL._contexts.clear()
            NODE_POOL.reserved = 0
            NODE_POOL._cond.notify_all()
    assert leaked == 0, (
        f"node memory pool leaked {leaked} bytes "
        f"(live contexts: {culprits})")


# a worker's memory maps, and the count past which its compiled programs
# are dropped. Every XLA:CPU executable a test compiles or reloads maps its
# code and stays mapped while the jit caches hold it — an 8-device mesh
# program maps thousands of regions — and at `vm.max_map_count` (65 530
# here) the next one's `mmap` fails inside the compiler or the cache's
# loader: a segmentation fault, the worker down, whichever test came next
# failed (`tests/test_distributed_queries.py` run in one process reaches
# it at its 26th test; under `--dist load` it is luck whether one worker
# gets that many of them). Dropping the caches unmaps (14 391 -> 776 maps,
# PR 43); the persistent cache keeps the recompiles short.
_MAPS_HIGH_WATER = 50_000


@pytest.fixture(autouse=True)
def compiled_programs_stay_mappable():
    yield
    try:
        with open("/proc/self/maps", "rb") as f:
            maps = sum(1 for _ in f)
    except OSError:         # no procfs: nothing to count, nothing to fear
        return
    if maps > _MAPS_HIGH_WATER:
        import gc

        from trino_tpu.exec import jit_cache
        jit_cache.clear()
        jax.clear_caches()
        gc.collect()
