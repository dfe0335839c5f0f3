"""chip_smoke.py — the quickest proof that the engine still starts on the chip.

One process on one TPU: starts `TrinoServer(LocalQueryRunner.tpch(schema))`
on a loopback port, serves TPC-H q6/q1/q3 (plus a literal variant and a
PREPARE/EXECUTE pair) over `POST /v1/statement`, and checks every answer
against a plain NumPy reference computed here from the host generator's
columns (`connector/tpch_gen.py` — the same rows the engine generates on
the device). Chip-or-fail: without a TPU it exits non-zero and prints no
result; it never sets JAX_PLATFORMS and never falls back.

    python chip_smoke.py                 # sf1 on one chip
    python chip_smoke.py --schema sf10   # same phases, 60M-row lineitem
    python chip_smoke.py --chips 4       # only the mesh path vs one device

Every line but the last is one JSON object per phase; walls and seconds
in them are a smoke's readings (one run, host clock), not benchmark
numbers. The last line is `{"ok": true, "device": {...}}`.

The phase functions take the schema and the expected platform so that
tests/test_chip_smoke.py drives the same phases at `tiny` on the CPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request

import numpy as np

Q6 = """
SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= DATE '{date}'
  AND l_shipdate < DATE '{date}' + INTERVAL '1' YEAR
  AND l_discount BETWEEN {disc} - 0.01 AND {disc} + 0.01
  AND l_quantity < {qty}
"""

Q6_PREPARED = """
SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= ?
  AND l_shipdate < ? + INTERVAL '1' YEAR
  AND l_discount BETWEEN ? - 0.01 AND ? + 0.01
  AND l_quantity < ?
"""

Q1 = """
SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sum_qty,
       sum(l_extendedprice) AS sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
       avg(l_quantity) AS avg_qty, avg(l_extendedprice) AS avg_price,
       avg(l_discount) AS avg_disc, count(*) AS count_order
FROM lineitem
WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '90' DAY
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus
"""

Q3 = """
SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue,
       o_orderdate, o_shippriority
FROM customer, orders, lineitem
WHERE c_mktsegment = 'BUILDING' AND c_custkey = o_custkey
  AND l_orderkey = o_orderkey AND o_orderdate < DATE '1995-03-15'
  AND l_shipdate > DATE '1995-03-15'
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate LIMIT 10
"""

JOIN_COUNT = ("SELECT count(*), sum(o_totalprice) FROM customer, orders "
              "WHERE c_custkey = o_custkey")

# (date, discount, quantity) literal sets: the q6 pair, then the EXECUTE pair
Q6_LITERALS = (("1994-01-01", "0.06", 24), ("1995-01-01", "0.07", 25))
EXECUTE_LITERALS = (("1993-01-01", "0.05", 23), ("1996-01-01", "0.04", 26))

# the columns each table must hold on the device for q6/q1/q3
SMOKE_COLUMNS = {
    "lineitem": ["l_orderkey", "l_quantity", "l_extendedprice",
                 "l_discount", "l_tax", "l_returnflag", "l_linestatus",
                 "l_shipdate"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "customer": ["c_custkey", "c_mktsegment"],
}


def q6_sql(literals) -> str:
    date, disc, qty = literals
    return Q6.format(date=date, disc=disc, qty=qty)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ------------------------------------------------------------------ device

def require_tpu(chips: int):
    """The devices to run on, or a non-zero exit: a smoke that cannot see
    the chip has nothing to say."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r} — not run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX found "
                 f"{len(devices)} device(s) — not run")
    return devices


def peak_bytes(device):
    stats = device.memory_stats()      # None on the CPU backend
    return None if not stats else int(stats["peak_bytes_in_use"])


def device_reading(devices) -> dict:
    import jax
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "jax": jax.__version__,
            "runtime": d.client.platform_version}


# ------------------------------------------------------------- HTTP client

class Client:
    """The documented client loop over the stdlib: POST /v1/statement,
    follow nextUri to the end. Every statement runs with the result
    cache off so that a repeat really executes on the device."""

    def __init__(self, base_uri: str):
        self.base_uri = base_uri

    def _open(self, req):
        with urllib.request.urlopen(req) as resp:
            return json.loads(resp.read()), dict(resp.headers)

    def statement(self, sql: str, headers=None):
        """-> (query id, column names, rows, response headers, wall_s);
        the wall is the host clock around the fully drained result."""
        t0 = time.perf_counter()
        req = urllib.request.Request(f"{self.base_uri}/v1/statement",
                                     data=sql.encode(), method="POST")
        req.add_header("X-Trino-User", "chip_smoke")
        req.add_header("X-Trino-Session", "result_cache_enabled=false")
        for k, v in (headers or {}).items():
            req.add_header(k, v)
        payload, hdrs = self._open(req)
        columns, rows = payload.get("columns"), list(payload.get("data", []))
        while "nextUri" in payload:
            payload, h = self._open(payload["nextUri"])
            hdrs.update(h)
            columns = payload.get("columns", columns)
            rows.extend(payload.get("data", []))
        wall = time.perf_counter() - t0
        if payload.get("error") is not None:
            raise RuntimeError(f"query failed: {payload['error']}\n{sql}")
        return (payload["id"], [c["name"] for c in columns or []], rows,
                hdrs, wall)

    def query_info(self, qid: str) -> dict:
        return self._open(f"{self.base_uri}/v1/query/{qid}")[0]

    def metrics(self) -> str:
        with urllib.request.urlopen(f"{self.base_uri}/v1/metrics") as resp:
            return resp.read().decode()


# -------------------------------------------------- plain NumPy reference

def _days(date: str) -> int:
    return int(np.datetime64(date, "D").astype(np.int64))


def _date(days: int) -> str:
    return str(np.datetime64(int(days), "D"))


def _dec(value: int, scale: int) -> str:
    """Scaled integer -> the wire's decimal text ('1227180.2380')."""
    sign, value = ("-", -value) if value < 0 else ("", value)
    return f"{sign}{value // 10 ** scale}.{value % 10 ** scale:0{scale}d}"


def _avg(total: int, count: int) -> int:
    """Decimal avg keeps the input scale, rounding half up."""
    return (2 * total + count) // (2 * count)


def host_columns(schema: str) -> dict:
    """The smoke's columns from the HOST generator, whole tables."""
    from trino_tpu.connector import tpch, tpch_gen as G
    sf = tpch.SCHEMAS[schema]
    cols = {}
    for table, names in SMOKE_COLUMNS.items():
        n = G.row_count(table, sf)
        for name in names:
            if G.string_kind(table, name):
                cols[name] = G.pool_values(table, name, sf)[
                    G.codes_chunk(table, sf, name, 0, n)]
            else:
                cols[name] = G.numeric_chunk(table, sf, name, 0, n)
    return cols


def ref_q6(c: dict, date: str, disc: str, qty: int) -> list:
    lo = _days(date)
    hi = _days(str(np.datetime64(date, "Y") + 1) + date[4:])
    d = round(float(disc) * 100)
    keep = ((c["l_shipdate"] >= lo) & (c["l_shipdate"] < hi)
            & (c["l_discount"] >= d - 1) & (c["l_discount"] <= d + 1)
            & (c["l_quantity"] < qty * 100))
    revenue = int(np.sum(c["l_extendedprice"][keep] * c["l_discount"][keep]))
    return [[_dec(revenue, 4)]]


def ref_q1(c: dict) -> list:
    keep = c["l_shipdate"] <= _days("1998-12-01") - 90
    flag, status = c["l_returnflag"][keep], c["l_linestatus"][keep]
    qty, price = c["l_quantity"][keep], c["l_extendedprice"][keep]
    disc, tax = c["l_discount"][keep], c["l_tax"][keep]
    disc_price = price * (100 - disc)
    groups, inv = np.unique(np.char.add(flag, status), return_inverse=True)
    rows = []
    for g, key in enumerate(groups):
        m = inv == g
        n = int(m.sum())
        sums = [int(x[m].sum()) for x in
                (qty, price, disc_price, disc_price * (100 + tax), disc)]
        rows.append([key[0], key[1], _dec(sums[0], 2), _dec(sums[1], 2),
                     _dec(sums[2], 4), _dec(sums[3], 6),
                     _dec(_avg(sums[0], n), 2), _dec(_avg(sums[1], n), 2),
                     _dec(_avg(sums[4], n), 2), n])
    return rows


def ref_q3(c: dict) -> list:
    cut = _days("1995-03-15")
    building = c["c_custkey"][c["c_mktsegment"] == "BUILDING"]
    omask = (c["o_orderdate"] < cut) & np.isin(c["o_custkey"], building)
    lmask = (c["l_shipdate"] > cut) \
        & np.isin(c["l_orderkey"], c["o_orderkey"][omask])
    keys, group = np.unique(c["l_orderkey"][lmask], return_inverse=True)
    revenue = np.zeros(len(keys), dtype=np.int64)
    np.add.at(revenue, group, c["l_extendedprice"][lmask]
              * (100 - c["l_discount"][lmask]))
    by_key = np.argsort(c["o_orderkey"])
    at = by_key[np.searchsorted(c["o_orderkey"], keys, sorter=by_key)]
    odate, prio = c["o_orderdate"][at], c["o_shippriority"][at]
    top = np.lexsort((keys, odate, -revenue))[:10]
    return [[int(keys[i]), _dec(int(revenue[i]), 4), _date(odate[i]),
             int(prio[i])] for i in top]


def check(name: str, got: list, want: list) -> None:
    """Row-for-row: decimals, keys, dates and counts exact; doubles to
    1e-9 relative."""
    assert len(got) == len(want), \
        f"{name}: {len(got)} rows, reference has {len(want)}"
    for i, (g, w) in enumerate(zip(got, want)):
        assert len(g) == len(w), f"{name} row {i}: {g} vs {w}"
        for a, b in zip(g, w):
            if isinstance(b, float):
                assert abs(a - b) <= 1e-9 * max(abs(b), 1e-300), \
                    f"{name} row {i}: {a} vs reference {b}"
            else:
                assert a == b, f"{name} row {i}: {g} vs reference {w}"


# ------------------------------------------------------------ served phase

def _served(client: Client, name: str, sql: str, want: list, device,
            headers=None) -> dict:
    """Run one statement over HTTP, check it, read its stats back from
    GET /v1/query/<id> and print the phase line."""
    qid, _, rows, _, wall = client.statement(sql, headers)
    check(name, rows, want)
    info = client.query_info(qid)
    stats = info["stats"]
    reading = {
        "query_id": qid, "rows": len(rows), "wall_s": wall,
        "compile_s": info["compileTimeMillis"] / 1000.0,
        "jit_misses": stats["jit_misses"],
        "jit_compiles": stats["jit_compiles"],
        "spilled_bytes": stats["spilled_bytes"],
        "retries": info["retries"],
        "peak_bytes_in_use": peak_bytes(device),
        "equals_reference": True,
    }
    emit(name, readings="smoke", **reading)
    return reading


def serve_phases(schema: str, platform: str) -> None:
    """Server up on a loopback port; q6, q1, q3 cold then warm; q6 with
    other literals; an EXECUTE pair; nodes and metrics. Raises on the
    first failure."""
    import jax

    from trino_tpu.exec import LocalQueryRunner
    from trino_tpu.server import TrinoServer

    device = jax.devices()[0]
    t0 = time.perf_counter()
    cols = host_columns(schema)
    emit("reference_columns", schema=schema,
         lineitem_rows=int(cols["l_orderkey"].shape[0]),
         orders_rows=int(cols["o_orderkey"].shape[0]),
         customer_rows=int(cols["c_custkey"].shape[0]),
         host_generate_s=time.perf_counter() - t0)

    # data load = the server's own table warmup (serve/warmup.py): the
    # columns are generated on the device and held there before the
    # first statement arrives
    manifest = {"tables": [
        {"table": f"tpch.{schema}.{t}", "columns": names}
        for t, names in SMOKE_COLUMNS.items()]}
    t0 = time.perf_counter()
    server = TrinoServer(LocalQueryRunner.tpch(schema),
                         warmup_manifest=manifest).start()
    try:
        load_s = time.perf_counter() - t0
        # `resident` false is an answer, not a failure: the table did not
        # fit the device table cache (sf10's lineitem) and its scans go
        # through the connector's device column cache instead
        for entry in server.warmup_report:
            assert "error" not in entry, f"table warmup failed: {entry}"
        emit("data_load", readings="smoke", seconds=load_s,
             tables=server.warmup_report,
             peak_bytes_in_use=peak_bytes(device))
        client = Client(server.base_uri)

        for name, sql, want in (
                ("q6", q6_sql(Q6_LITERALS[0]), ref_q6(cols, *Q6_LITERALS[0])),
                ("q1", Q1, ref_q1(cols)),
                ("q3", Q3, ref_q3(cols))):
            _served(client, f"{name}_cold", sql, want, device)
            warm = _served(client, f"{name}_warm", sql, want, device)
            assert warm["jit_misses"] == 0, f"{name} warm run recompiled"

        # same shape, other literals: literal hoisting must reuse q6's
        # kernels (PR 4)
        variant = _served(
            client, "q6_other_literals", q6_sql(Q6_LITERALS[1]),
            ref_q6(cols, *Q6_LITERALS[1]), device)
        assert variant["jit_misses"] == 0, \
            "q6 with other literals compiled new kernels"

        # PREPARE once, EXECUTE twice with different values (PR 5): the
        # stateless client re-sends the statement it was handed back
        _, _, _, hdrs, _ = client.statement(
            f"PREPARE smoke_q6 FROM {Q6_PREPARED}")
        prepared = {"X-Trino-Prepared-Statement":
                    hdrs["X-Trino-Added-Prepare"]}
        for i, (date, disc, qty) in enumerate(EXECUTE_LITERALS):
            run = _served(
                client, f"execute_{i + 1}",
                f"EXECUTE smoke_q6 USING DATE '{date}', DATE '{date}', "
                f"{disc}, {disc}, {qty}",
                ref_q6(cols, date, disc, qty), device, headers=prepared)
        assert run["jit_misses"] == 0, \
            "second EXECUTE compiled new kernels"

        # the engine's own view of the node it runs on
        _, names, rows, _, _ = client.statement(
            "SELECT * FROM system.runtime.nodes")
        nodes = [dict(zip(names, row)) for row in rows]
        budget = "default" if platform == "cpu" else "measured"
        assert nodes and nodes[0]["node_id"].startswith(platform + "-"), \
            f"system.runtime.nodes names no {platform} device: {nodes}"
        assert nodes[0]["pool_budget_source"] == budget, \
            f"node pool budget_source is not {budget!r}: {nodes[0]}"
        metrics = client.metrics()
        assert "trino_tpu_pool_limit_bytes" in metrics \
            and "trino_tpu_jit_cache" in metrics, "/v1/metrics is incomplete"
        emit("node", nodes=nodes, metrics_lines=len(metrics.splitlines()))
    finally:
        server.stop()


# -------------------------------------------------------------- mesh phase

def mesh_phases(schema: str, devices) -> None:
    """The mesh path on `devices` against LocalQueryRunner on one device,
    over the same data: rows equal, collectives in-program, shard i's
    pages on device i, every device holds memory."""
    from trino_tpu.exec import LocalQueryRunner
    from trino_tpu.exec.distributed import (DistributedQueryRunner,
                                            ShardExecutionPlanner)
    from trino_tpu.planner import LogicalPlanner
    from trino_tpu.planner.optimizer import optimize
    from trino_tpu.sql import parse_statement

    n = len(devices)
    local = LocalQueryRunner.tpch(schema)
    dist = DistributedQueryRunner.tpch(schema, devices=devices)
    for name, sql in (("q1", Q1), ("q3", Q3), ("join_count", JOIN_COUNT)):
        if name == "join_count":
            dist.execute(
                "SET SESSION join_distribution_type = 'PARTITIONED'")
        t0 = time.perf_counter()
        want = local.execute(sql).rows
        local_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = dist.execute(sql).rows
        mesh_s = time.perf_counter() - t0
        stats = dist.last_query_stats
        assert got == want, f"mesh {name}: {got}\nvs one device:\n{want}"
        assert stats["mesh_devices"] == n, \
            f"mesh {name}: mesh_devices {stats['mesh_devices']} != {n}"
        assert stats["exchanges_staged"] == 0, \
            f"mesh {name}: {stats['exchanges_staged']} exchanges left " \
            "the program for the host"
        emit(f"mesh_{name}", readings="smoke", rows=len(got),
             equals_one_device=True, local_wall_s=local_s,
             mesh_wall_s=mesh_s, mesh_devices=stats["mesh_devices"],
             exchanges_fused=stats["exchanges_fused"],
             exchanges_staged=stats["exchanges_staged"],
             exchange_rows=stats["exchange_rows"],
             jit_misses=stats["jit_misses"],
             compile_s=stats["compile_time_ms"] / 1000.0)

    # placement: each shard's leaf pages live on ITS device
    stmt = parse_statement("SELECT o_orderkey FROM orders")
    plan = optimize(LogicalPlanner(dist.metadata, dist.session).plan(stmt),
                    dist.metadata, dist.session, distributed=True)
    for shard in range(n):
        ex = ShardExecutionPlanner(dist.metadata, dist.session, shard,
                                   dist.mesh.n, {},
                                   device=dist.mesh.device_of(shard))
        page = next(iter(ex.execute(plan.source).iter_pages()), None)
        assert page is not None, f"shard {shard} scanned no page"
        on = list(page.columns[0].values.devices())[0]
        assert on == dist.mesh.device_of(shard), \
            f"shard {shard} pages on {on}"
    peaks = [peak_bytes(d) for d in devices]
    if devices[0].platform != "cpu":
        assert all(p and p > (1 << 20) for p in peaks), \
            f"some device held no pages: peak_bytes_in_use {peaks}"
    emit("mesh_placement", shards_on_own_device=n, peak_bytes_in_use=peaks)


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--schema", default="sf1", choices=("sf1", "sf10"))
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    import trino_tpu
    trino_tpu.enable_persistent_cache()
    reading = device_reading(devices)
    emit("device", **reading)
    if args.chips == 1:
        serve_phases(args.schema, "tpu")
    else:
        mesh_phases(args.schema, devices[:args.chips])
    print(json.dumps({"ok": True, "device": {
        "platform": reading["platform"], "kind": reading["kind"],
        "count": reading["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
