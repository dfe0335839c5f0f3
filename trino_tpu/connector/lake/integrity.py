"""lake_fsck: offline integrity walk + rollback repair + orphan GC.

The recovery half of the data-plane integrity contract (connector.py
records digests at commit and verifies at read; this module answers
"the verify failed — now what"). One walk per table, strictly from the
outside in:

  pointer -> manifest-<v>.json -> data files -> row groups

  - A torn or corrupt POINTER (unparseable json, missing manifest file,
    manifest digest mismatch) is ROLLED BACK: the newest retained
    `manifest-<v>.json` that is fully intact (parseable, every
    referenced data file present with a matching physical digest)
    becomes the pointer target again. Because `committed_tokens` ride
    inside each manifest version, the exactly-once write ledger rolls
    back WITH the file list — a replayed token from after the rollback
    point commits again, exactly once.
  - A corrupt DATA FILE in an otherwise-intact current version is
    reported (and stays quarantined): fsck cannot invent the bytes
    back. Rolling back would discard sibling commits, so that is the
    operator's call — the report names the intact versions.
  - Orphan GC rides the same walk: files under data/ referenced by NO
    retained manifest version and older than `gc_grace_s` are removed
    (the grace age keeps an in-flight sink's freshly-staged files
    safe — they are referenced only at finish()). Stale commit temp
    files age out the same way.
  - The per-process quarantine ledger is reconciled: entries whose file
    now verifies clean or no longer exists are cleared.

Surfaced as `LakeConnector.fsck()` and `runner.lake_fsck()`.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Tuple

from trino_tpu.connector.lake import format as F
from trino_tpu.connector.lake.connector import (
    DATA_DIR, MANIFEST, _MANIFEST_V, clear_quarantine, quarantined_files)
from trino_tpu.connector.spi import SchemaTableName

# orphans younger than this are NEVER collected: an open sink's staged
# files are unreferenced until its commit swaps the pointer
DEFAULT_GC_GRACE_S = 15 * 60


def _load_json(path: str) -> Optional[dict]:
    try:
        with open(path, "rb") as f:
            return json.loads(f.read())
    except (OSError, ValueError):
        return None


def _retained_versions(tdir: str) -> List[Tuple[int, str]]:
    """[(version, path)] of every manifest-<v>.json on disk, newest
    first."""
    out = []
    try:
        for entry in os.scandir(tdir):
            m = _MANIFEST_V.match(entry.name)
            if m:
                out.append((int(m.group(1)), entry.path))
    except OSError:
        pass
    out.sort(reverse=True)
    return out


def _verify_manifest_files(tdir: str, manifest: dict,
                           deep: bool) -> List[dict]:
    """Verify every data file a manifest references; returns a list of
    problem records (empty = fully intact). Physical digest first (it
    covers the whole byte stream); `deep` additionally re-decodes and
    checks per-(group, column) content digests — catches a manifest
    whose recorded file digest was itself corrupted in place."""
    problems = []
    fmt = manifest.get("format")
    group_rows = int(manifest.get("row_group_rows",
                                  F.DEFAULT_ROW_GROUP_ROWS))
    all_names = [c["name"] for c in manifest.get("columns") or []]
    for entry in manifest.get("files", ()):
        path = os.path.join(tdir, entry["path"])
        if not os.path.isfile(path):
            problems.append({"path": entry["path"], "kind": "missing"})
            continue
        want = entry.get("digest")
        if want:
            got, nbytes = F.file_digest(path)
            if got != want or (entry.get("bytes") is not None
                               and nbytes != int(entry["bytes"])):
                problems.append({"path": entry["path"],
                                 "kind": "file_digest_mismatch"})
                continue
        if not deep:
            continue
        ngroups = len(entry.get("groups") or [])
        if ngroups == 0:
            continue
        try:
            got_cols = F.read_groups(path, fmt, all_names, all_names,
                                     list(range(ngroups)),
                                     group_rows=group_rows)
        except Exception as e:  # noqa: BLE001 — classify, don't crash
            problems.append({"path": entry["path"], "kind": "undecodable",
                             "error": f"{type(e).__name__}: {e}"})
            continue
        off = 0
        bad = None
        for g, meta in enumerate(entry["groups"]):
            rows = int(meta.get("rows", 0))
            for name, want_dg in (meta.get("digests") or {}).items():
                arr, valid = got_cols[name]
                have = F.column_chunk_digest(
                    arr[off:off + rows],
                    None if valid is None else valid[off:off + rows])
                if have != want_dg:
                    bad = {"path": entry["path"],
                           "kind": "group_digest_mismatch",
                           "group": g, "column": name}
                    break
            if bad:
                break
            off += rows
        if bad:
            problems.append(bad)
    return problems


def _write_pointer(tdir: str, version: int, vpath: str) -> None:
    import hashlib
    import uuid
    with open(vpath, "rb") as f:
        raw = f.read()
    pointer = {"pointer_version": 1, "version": int(version),
               "path": os.path.basename(vpath),
               "digest": hashlib.blake2b(raw, digest_size=16).hexdigest()}
    path = os.path.join(tdir, MANIFEST)
    tmp = f"{path}.tmp.{uuid.uuid4().hex[:8]}"
    with open(tmp, "w") as f:
        json.dump(pointer, f)
    os.replace(tmp, path)


def _fsck_table(md, name: SchemaTableName, repair: bool, deep: bool,
                now: float, gc_grace_s: float,
                gc: bool) -> dict:
    tdir = md.table_dir(name)
    report: dict = {"table": f"{name.schema}.{name.table}", "ok": True,
                    "problems": [], "rolled_back_to": None,
                    "orphans_removed": [], "orphans_kept": 0}
    retained = _retained_versions(tdir)

    # ---- pointer -> manifest chain ---------------------------------
    pointer = _load_json(os.path.join(tdir, MANIFEST))
    manifest = None
    chain_broken = None
    if pointer is None:
        chain_broken = "torn_pointer"
    elif "columns" in pointer:
        manifest = pointer      # legacy single-file manifest
    else:
        vpath = os.path.join(tdir, os.path.basename(
            str(pointer.get("path") or "")))
        raw = None
        try:
            with open(vpath, "rb") as f:
                raw = f.read()
        except OSError:
            chain_broken = "missing_manifest"
        if raw is not None:
            import hashlib
            digest = hashlib.blake2b(raw, digest_size=16).hexdigest()
            if pointer.get("digest") and digest != pointer["digest"]:
                chain_broken = "manifest_digest_mismatch"
            else:
                try:
                    manifest = json.loads(raw)
                except ValueError:
                    chain_broken = "undecodable_manifest"

    # ---- verify (or roll back) -------------------------------------
    if manifest is not None:
        problems = _verify_manifest_files(tdir, manifest, deep)
        if problems:
            report["ok"] = False
            report["problems"] = problems
    else:
        report["ok"] = False
        report["problems"] = [{"kind": chain_broken}]
        if repair:
            # ROLLBACK: newest retained version that is fully intact
            for version, vpath in retained:
                cand = _load_json(vpath)
                if cand is None or "columns" not in cand:
                    continue
                if _verify_manifest_files(tdir, cand, deep):
                    continue
                _write_pointer(tdir, version, vpath)
                with md._lock:
                    md._cache.pop(name, None)
                manifest = cand
                report["rolled_back_to"] = version
                report["ok"] = True
                break

    # ---- orphan GC --------------------------------------------------
    referenced = set()
    for _, vpath in retained:
        cand = _load_json(vpath)
        if cand:
            referenced.update(e["path"] for e in cand.get("files", ()))
    if manifest is not None:
        referenced.update(e["path"] for e in manifest.get("files", ()))
    ddir = os.path.join(tdir, DATA_DIR)
    try:
        data_files = sorted(os.listdir(ddir))
    except OSError:
        data_files = []
    for fname in data_files:
        rel = f"{DATA_DIR}/{fname}"
        if rel in referenced:
            continue
        fpath = os.path.join(ddir, fname)
        try:
            age = now - os.stat(fpath).st_mtime
        except OSError:
            continue
        if not gc or not repair or age < gc_grace_s:
            report["orphans_kept"] += 1
            continue
        try:
            os.remove(fpath)
            clear_quarantine(fpath)
            report["orphans_removed"].append(rel)
        except OSError:
            report["orphans_kept"] += 1
    # stale commit temp files (a crashed writer's torn tmp) age out too
    try:
        for entry in os.scandir(tdir):
            if ".json.tmp." in entry.name and gc and repair:
                if now - entry.stat().st_mtime >= gc_grace_s:
                    os.remove(entry.path)
    except OSError:
        pass

    # ---- quarantine reconciliation ---------------------------------
    bad_paths = {os.path.abspath(os.path.join(tdir, p["path"]))
                 for p in report["problems"] if "path" in p}
    for qpath in quarantined_files():
        if not qpath.startswith(os.path.abspath(tdir) + os.sep):
            continue
        if not os.path.isfile(qpath) or qpath not in bad_paths:
            # gone, or re-verified clean by this walk
            clear_quarantine(qpath)
    report["retained_versions"] = [v for v, _ in retained]
    return report


def lake_fsck(metadata, repair: bool = True, deep: bool = True,
              gc: bool = True,
              gc_grace_s: float = DEFAULT_GC_GRACE_S) -> dict:
    """Walk every table of the lake catalog; returns the full report.

    repair=False is a dry run (report only — no rollback, no GC).
    deep=True re-decodes every file and checks per-(group, column)
    content digests; deep=False stops at physical file digests."""
    base = metadata.base_dir
    now = time.time()
    tables = []
    try:
        schemas = sorted(os.listdir(base))
    except OSError:
        schemas = []
    for schema in schemas:
        sdir = os.path.join(base, schema)
        # `_mv` (and any future underscore sibling) is metadata, not a
        # schema: its flat record files are never GC candidates
        if not os.path.isdir(sdir) or schema.startswith("_"):
            continue
        for table in sorted(os.listdir(sdir)):
            tdir = os.path.join(sdir, table)
            if not os.path.isdir(tdir):
                continue
            has_pointer = os.path.exists(os.path.join(tdir, MANIFEST))
            if not has_pointer and not _retained_versions(tdir):
                continue
            tables.append(_fsck_table(
                metadata, SchemaTableName(schema, table), repair, deep,
                now, gc_grace_s, gc))
    return {
        "ok": all(t["ok"] for t in tables),
        "tables": tables,
        "tables_checked": len(tables),
        "rolled_back": [t["table"] for t in tables
                        if t["rolled_back_to"] is not None],
        "orphans_removed": sum(len(t["orphans_removed"])
                               for t in tables),
        "quarantined": len(quarantined_files()),
    }
