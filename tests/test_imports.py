"""Import hygiene: every trino_tpu module imports cleanly in isolation.

The observability layer threads through runner, planner, tracker, server,
and connectors — exactly the shape that breeds circular imports that only
bite when a module is imported FIRST (e.g. a tool importing
trino_tpu.obs.metrics before trino_tpu.exec). Simulate first-import for
each module by stripping every trino_tpu entry from sys.modules and
importing just that module; the original module objects are restored
afterwards so identity-sensitive state (TRACKER, NODE_POOL, jit cache)
is untouched for the rest of the suite.
"""

import importlib
import pathlib
import sys

import pytest

import trino_tpu

_ROOT = pathlib.Path(trino_tpu.__file__).parent


def _all_modules():
    mods = ["trino_tpu"]
    for path in sorted(_ROOT.rglob("*.py")):
        rel = path.relative_to(_ROOT)
        parts = list(rel.parts[:-1])
        stem = rel.stem
        if stem != "__init__":
            parts.append(stem)
        if parts:
            mods.append("trino_tpu." + ".".join(parts))
    return mods


MODULES = _all_modules()


def test_module_inventory_sane():
    assert "trino_tpu.obs.metrics" in MODULES
    assert "trino_tpu.exec.runner" in MODULES
    assert len(MODULES) > 30


@pytest.mark.parametrize("module", MODULES)
def test_module_imports_in_isolation(module):
    saved = {name: mod for name, mod in sys.modules.items()
             if name == "trino_tpu" or name.startswith("trino_tpu.")}
    for name in list(saved):
        del sys.modules[name]
    try:
        importlib.import_module(module)
    finally:
        # drop the freshly-created duplicates, restore the originals
        for name in list(sys.modules):
            if name == "trino_tpu" or name.startswith("trino_tpu."):
                del sys.modules[name]
        sys.modules.update(saved)


def test_imports_without_pyarrow():
    """pyarrow is STRICTLY optional: with its import blocked (the
    no-pyarrow machine, simulated via sys.modules = None -> ImportError
    on import), every module — the lake connector included — still
    imports, and the lake falls back to the .npz native format."""
    saved = {name: mod for name, mod in sys.modules.items()
             if name == "trino_tpu" or name.startswith("trino_tpu.")}
    arrow_saved = {name: mod for name, mod in sys.modules.items()
                   if name == "pyarrow" or name.startswith("pyarrow.")}
    for name in list(saved) + list(arrow_saved):
        del sys.modules[name]
    sys.modules["pyarrow"] = None   # import pyarrow -> ImportError
    try:
        fmt = importlib.import_module("trino_tpu.connector.lake.format")
        assert fmt.HAVE_PYARROW is False
        assert fmt.default_format() == "npz"
        lake = importlib.import_module("trino_tpu.connector.lake")
        assert lake.HAVE_PYARROW is False
        # the rest of the engine imports clean without pyarrow too
        importlib.import_module("trino_tpu.exec.runner")
    finally:
        for name in list(sys.modules):
            if name == "trino_tpu" or name.startswith("trino_tpu.") \
                    or name == "pyarrow" or name.startswith("pyarrow."):
                del sys.modules[name]
        sys.modules.update(saved)
        sys.modules.update(arrow_saved)


def test_lake_npz_works_without_pyarrow(tmp_path):
    """Functional fallback proof (not just import hygiene): a connector
    forced to the npz format writes/prunes/reads with pyarrow blocked —
    tier-1 still collects AND the lake still serves on that machine."""
    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.connector.lake import format as F
    from trino_tpu.predicate import Domain, Range, TupleDomain
    real = F.HAVE_PYARROW
    try:
        F.HAVE_PYARROW = False
        assert F.default_format() == "npz"
        from trino_tpu.connector import lake
        from trino_tpu.connector.spi import (ColumnMetadata,
                                             SchemaTableName,
                                             TableMetadata)
        from trino_tpu.page import Column, Page
        conn = lake.create_connector(str(tmp_path / "lk"))
        name = SchemaTableName("default", "t")
        conn.metadata.create_table(TableMetadata(
            name, (ColumnMetadata("k", T.BIGINT),)))
        h = conn.metadata.get_table_handle(name)
        sink = conn.page_sink(h, write_token="w1")
        sink.append_page(Page((Column.from_numpy(
            np.arange(10, dtype=np.int64), T.BIGINT),), 10))
        sink.finish()
        total = sum(int(p.num_rows) for s in
                    conn.split_manager.get_splits(h)
                    for p in conn.page_source.pages(
                        s, conn.metadata.get_column_handles(h), 16))
        assert total == 10
        kept, pruned = lake.eligible_files(
            conn._metadata.load_manifest(name),
            TupleDomain.with_column_domains(
                {"k": Domain.from_range(T.BIGINT,
                                        Range.greater_than(50))}))
        assert kept == [] and pruned == 1
    finally:
        F.HAVE_PYARROW = real


def test_imports_initialise_no_backend():
    """A chip belongs to one process: importing the package — every
    module of it, as the fleet parent, its workers and every tool do —
    must not touch a device. A module-level `jnp` constant is enough to
    initialise the backend and take the chip from the engine."""
    import subprocess
    code = (
        "import importlib, sys\n"
        "from jax._src import xla_bridge\n"
        f"for m in {MODULES!r}:\n"
        "    importlib.import_module(m)\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=str(_ROOT.parent), timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
