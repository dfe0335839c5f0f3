"""Benchmark: the BASELINE.md measurement ladder on the real chip.

Rungs (BASELINE.md): #1 q6 tiny-smoke folds into the SF1 run; #2 q1 SF1
(lineitem hash aggregation); #3 q3 SF10 (3-way join); #4 q9 SF100 (6-way
join + partial agg — exercises the spill path: >threshold builds keep only
sorted keys in HBM); #5 TPC-DS SF100 q64/q72 (wide star joins, skewed
keys). Plus the BASELINE metric hash-join probe rows/sec/chip, measured on
a dedicated SF10 lineitem-orders join. Every query runs through the full
engine (parse -> plan -> optimize -> execute). Prints ONE JSON line; the
headline metric stays q6 SF1 wall-clock with the other rungs in "extra".

SF100 rungs run in FRESH SUBPROCESSES (one per rung): the reference's
benchmark discipline separates prewarm from measurement per run
(trino-benchto-benchmarks), and an in-process run after the warm SF1/SF10
runners carries device-state residue (scan caches, kernel workspaces,
fragment intermediates) that made the rungs irreproducible in round 4.
A child prints one JSON line on stdout; the parent merges it.

vs_baseline: the reference repo publishes no numbers (BASELINE.md); the
denominators are ballpark single-node Trino wall-clocks from its
LocalQueryRunner-style benchmarks on server CPUs — q6 SF1 ~1.0s, q1 SF1
~2.5s, q3 SF10 ~10s, q9 SF100 ~100s, q64/q72 SF100 ~120s/~200s — so
vs_baseline > 1 means faster than that estimate. SF100 rungs run ONCE
(they stream 100GB-scale generated data through one chip).

Data scope (BASELINE.md north-star asks for bit-identical rows): the tpch
connector generates seekable spec-shaped hash-stream data, not dbgen
bitstreams (the airlift/dbgen seed tables are not in the reference repo
and cannot be fetched offline — see connector/tpch_gen.py), so the
comparison is same-shape wall-clock, not row-identical output.
"""

import json
import os
import subprocess
import sys
import time

# total wall budget: SF100 rungs are skipped once exceeded so the JSON
# line ALWAYS prints (a single runaway rung must not eat the whole bench)
BUDGET_S = int(os.environ.get("TRINO_TPU_BENCH_BUDGET_S", 5400))
_T0 = time.monotonic()


def _remaining() -> float:
    return BUDGET_S - (time.monotonic() - _T0)


def _ensure_backend() -> str:
    """The platform this process runs on: what JAX finds. No probe and no
    fallback — a backend that cannot initialise is an error the bench
    dies of, not a reason to report CPU numbers in its place."""
    import jax
    return jax.devices()[0].platform

Q6 = """
SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= DATE '1994-01-01'
  AND l_shipdate < DATE '1994-01-01' + INTERVAL '1' YEAR
  AND l_discount BETWEEN 0.06 - 0.01 AND 0.06 + 0.01
  AND l_quantity < 24
"""

# literal-variant probes (round 8, parameterized kernel compilation): the
# measured query re-run with every hoistable numeric/date constant
# perturbed. With literal hoisting the variant reuses the warm shape's XLA
# executables, so variant_jit_misses must read 0 and variant_warm_wall_s
# tracks the warm median instead of paying a cold compile — the headline
# number for the dashboards-and-point-filters workload.
Q6_VARIANT = """
SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= DATE '1995-01-01'
  AND l_shipdate < DATE '1995-01-01' + INTERVAL '1' YEAR
  AND l_discount BETWEEN 0.07 - 0.01 AND 0.07 + 0.01
  AND l_quantity < 25
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

Q1_VARIANT = Q1.replace("INTERVAL '90' DAY", "INTERVAL '60' DAY")

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

Q3_VARIANT = Q3.replace("DATE '1995-03-15'", "DATE '1995-03-08'")

# prepared-statement probes (round 10): the measured query PREPAREd with
# its hoistable constants as `?` markers, EXECUTEd twice with different
# USING values. The second EXECUTE is the statement-reuse fast path —
# plan cache hit + parameter binding into warm kernels — measured against
# re-submitting the identical query as plain SQL (which re-plans).
# (name, prepare_sql, warm USING, perturbed USING, plain-SQL resubmit)
PREPARED = {
    "tpch_q6_sf1": (
        "bench_q6",
        Q6.replace("DATE '1994-01-01'", "?")
          .replace("0.06", "?").replace("l_quantity < 24",
                                        "l_quantity < ?"),
        "DATE '1994-01-01', DATE '1994-01-01', 0.06, 0.06, 24",
        "DATE '1995-01-01', DATE '1995-01-01', 0.07, 0.07, 25",
        Q6_VARIANT),
    "tpch_q1_sf1": (
        "bench_q1",
        Q1.replace("INTERVAL '90' DAY", "?"),
        "INTERVAL '90' DAY", "INTERVAL '60' DAY", Q1_VARIANT),
    "tpch_q3_sf10": (
        "bench_q3",
        Q3.replace("DATE '1995-03-15'", "?"),
        "DATE '1995-03-15', DATE '1995-03-15'",
        "DATE '1995-03-08', DATE '1995-03-08'", Q3_VARIANT),
}

JOIN_MICRO = """
SELECT count(*) FROM lineitem, orders WHERE l_orderkey = o_orderkey
"""

Q5 = """
SELECT n_name, sum(l_extendedprice * (1 - l_discount)) AS revenue
FROM customer, orders, lineitem, supplier, nation, region
WHERE c_custkey = o_custkey AND l_orderkey = o_orderkey
  AND l_suppkey = s_suppkey AND c_nationkey = s_nationkey
  AND s_nationkey = n_nationkey AND n_regionkey = r_regionkey
  AND r_name = 'ASIA' AND o_orderdate >= DATE '1994-01-01'
  AND o_orderdate < DATE '1994-01-01' + INTERVAL '1' YEAR
GROUP BY n_name ORDER BY revenue DESC
"""

Q9 = """
SELECT nation, o_year, sum(amount) AS sum_profit FROM (
  SELECT n_name AS nation, extract(year FROM o_orderdate) AS o_year,
         l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity
           AS amount
  FROM part, supplier, lineitem, partsupp, orders, nation
  WHERE s_suppkey = l_suppkey AND ps_suppkey = l_suppkey
    AND ps_partkey = l_partkey AND p_partkey = l_partkey
    AND o_orderkey = l_orderkey AND s_nationkey = n_nationkey
    AND p_name LIKE '%green%') AS profit
GROUP BY nation, o_year ORDER BY nation, o_year DESC
"""

Q72 = """
SELECT i_item_desc, w_warehouse_name, d1.d_week_seq,
       sum(CASE WHEN p_promo_sk IS NULL THEN 1 ELSE 0 END) no_promo,
       sum(CASE WHEN p_promo_sk IS NOT NULL THEN 1 ELSE 0 END) promo,
       count(*) total_cnt
FROM catalog_sales
JOIN inventory ON (cs_item_sk = inv_item_sk)
JOIN warehouse ON (w_warehouse_sk = inv_warehouse_sk)
JOIN item ON (i_item_sk = cs_item_sk)
JOIN customer_demographics ON (cs_bill_cdemo_sk = cd_demo_sk)
JOIN household_demographics ON (cs_bill_hdemo_sk = hd_demo_sk)
JOIN date_dim d1 ON (cs_sold_date_sk = d1.d_date_sk)
JOIN date_dim d2 ON (inv_date_sk = d2.d_date_sk)
JOIN date_dim d3 ON (cs_ship_date_sk = d3.d_date_sk)
LEFT JOIN promotion ON (cs_promo_sk = p_promo_sk)
LEFT JOIN catalog_returns ON (cr_item_sk = cs_item_sk
                              AND cr_order_number = cs_order_number)
WHERE d1.d_week_seq = d2.d_week_seq
  AND inv_quantity_on_hand < cs_quantity
  AND d3.d_date > d1.d_date + INTERVAL '5' DAY
  AND hd_buy_potential = '>10000'
  AND d1.d_year = 1999
  AND cd_marital_status = 'D'
GROUP BY i_item_desc, w_warehouse_name, d1.d_week_seq
ORDER BY total_cnt DESC, i_item_desc, w_warehouse_name, d1.d_week_seq
LIMIT 100
"""

Q64 = """
WITH cs_ui AS (
  SELECT cs_item_sk,
         sum(cs_ext_list_price) AS sale,
         sum(cr_refunded_cash + cr_return_amount) AS refund
  FROM catalog_sales, catalog_returns
  WHERE cs_item_sk = cr_item_sk AND cs_order_number = cr_order_number
  GROUP BY cs_item_sk
  HAVING sum(cs_ext_list_price) > 2 * sum(cr_refunded_cash
                                          + cr_return_amount))
SELECT i_product_name, s_store_name, s_zip, d1.d_year,
       count(*) AS cnt,
       sum(ss_wholesale_cost) AS s1, sum(ss_list_price) AS s2,
       sum(ss_coupon_amt) AS s3
FROM store_sales, store_returns, cs_ui, date_dim d1,
     customer, customer_demographics cd1, household_demographics hd1,
     customer_address ad1, income_band ib1, item, store
WHERE ss_store_sk = s_store_sk
  AND ss_sold_date_sk = d1.d_date_sk
  AND ss_customer_sk = c_customer_sk
  AND ss_cdemo_sk = cd1.cd_demo_sk
  AND ss_hdemo_sk = hd1.hd_demo_sk
  AND ss_addr_sk = ad1.ca_address_sk
  AND ss_item_sk = i_item_sk
  AND ss_item_sk = sr_item_sk
  AND ss_ticket_number = sr_ticket_number
  AND ss_item_sk = cs_ui.cs_item_sk
  AND hd1.hd_income_band_sk = ib1.ib_income_band_sk
  AND i_color IN ('maroon', 'burnished', 'dim', 'steel', 'navajo',
                  'chocolate')
  AND i_current_price BETWEEN 35 AND 45
GROUP BY i_product_name, s_store_name, s_zip, d1.d_year
ORDER BY i_product_name, s_store_name, cnt LIMIT 100
"""

# ballpark single-node Java-engine estimates (no published numbers exist)
BASE_Q6_SF1_S = 1.0
BASE_Q1_SF1_S = 2.5
BASE_Q3_SF10_S = 10.0
BASE_Q9_SF100_S = 100.0
BASE_Q64_SF100_S = 120.0
BASE_Q72_SF100_S = 200.0
BASE_JOIN_ROWS_PER_S = 50e6     # ballpark single-node probe throughput

# per-rung literal variants; None = the query has no hoistable constants
# (q9's only constant is a LIKE pattern, which stays static by design)
Q64_VARIANT = Q64.replace("BETWEEN 35 AND 45", "BETWEEN 36 AND 46")
Q72_VARIANT = Q72.replace("d1.d_year = 1999", "d1.d_year = 2000") \
                 .replace("INTERVAL '5' DAY", "INTERVAL '6' DAY")

SF100_RUNGS = {
    "tpch_q9_sf100": (BASE_Q9_SF100_S, "tpch", Q9, None),
    "tpcds_q64_sf100": (BASE_Q64_SF100_S, "tpcds", Q64, Q64_VARIANT),
    "tpcds_q72_sf100": (BASE_Q72_SF100_S, "tpcds", Q72, Q72_VARIANT),
}


def _sf100_runner(catalog: str):
    import trino_tpu
    trino_tpu.enable_persistent_cache()
    from trino_tpu.connector import tpch as tpch_conn
    from trino_tpu.exec import LocalQueryRunner
    # shrink the scan cache so join state owns the HBM, and stream probes
    # in smaller buffers (wide-buffer probe sorts exhaust per-op scratch —
    # round-4 measurement)
    tpch_conn.set_device_cache_budget(1 << 30)
    runner = LocalQueryRunner.tpch("sf100")
    if catalog == "tpcds":
        runner.execute("USE tpcds.sf100")
    runner.execute("SET SESSION probe_coalesce_rows = 4194304")
    return runner


def run_rung(tag: str) -> None:
    """Child mode: execute ONE SF100 rung in this (fresh) process and
    print a single JSON line {"wall_s": ...} or {"error": ...}."""
    base, catalog, sql, variant = SF100_RUNGS[tag]
    _ensure_backend()
    try:
        runner = _sf100_runner(catalog)
        t0 = time.perf_counter()
        rows = runner.execute(sql).rows
        wall = time.perf_counter() - t0
        if tag == "tpch_q9_sf100":
            assert rows, "q9 returned no rows"
        breakdown = _stats_breakdown(runner.last_query_stats)
        if variant is not None and _remaining() > 120:
            breakdown.update(_literal_variant(runner, variant))
        print(json.dumps({"wall_s": round(wall, 2),
                          "retries": runner.stats["retries"],
                          "faults_injected":
                              runner.stats["faults_injected"],
                          "breakdown": breakdown}),
              flush=True)
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the rung must report,
        # not die: even a SystemExit from backend init becomes a parsed
        # error line (the parent merges it as {tag}_error)
        print(json.dumps(
            {"error": f"{type(e).__name__}: {str(e)[:160]}"}), flush=True)


def _run_rung_subprocess(extra: dict, tag: str, base: float) -> None:
    """Launch `python bench.py --rung TAG` and merge its JSON line."""
    timeout = _remaining()
    if timeout < 60:
        extra[f"{tag}_error"] = \
            f"skipped: bench wall budget ({BUDGET_S}s) exhausted"
        return
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--rung", tag],
            capture_output=True, text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        extra[f"{tag}_error"] = \
            f"timeout: exceeded bench wall budget ({BUDGET_S}s)"
        return
    # one malformed child line must cost ONE rung, not the whole bench
    try:
        line = None
        for ln in reversed(proc.stdout.strip().splitlines()):
            ln = ln.strip()
            if ln.startswith("{"):
                line = ln
                break
        if line is None:
            tail = (proc.stderr or proc.stdout or "").strip()[-200:]
            extra[f"{tag}_error"] = \
                f"rung subprocess rc={proc.returncode}: {tail}"
            return
        got = json.loads(line)
        if "error" in got:
            extra[f"{tag}_error"] = got["error"]
        else:
            wall = float(got["wall_s"])
            extra[f"{tag}_wall_s"] = wall
            extra[f"{tag}_vs_baseline"] = round(base / wall, 3)
            if got.get("retries"):
                extra[f"{tag}_retries"] = int(got["retries"])
            if got.get("faults_injected"):
                extra[f"{tag}_faults_injected"] = int(got["faults_injected"])
            if got.get("breakdown"):
                extra[f"{tag}_breakdown"] = got["breakdown"]
    except Exception as e:  # noqa: BLE001
        extra[f"{tag}_error"] = f"rung result parse: {type(e).__name__}: {e}"


def _time_query(runner, sql, iters=3, breakdown=None, variant=None,
                prepared=None):
    t0 = time.perf_counter()
    rows = runner.execute(sql).rows  # warm-up (compile) run, untimed
    cold = time.perf_counter() - t0
    assert rows, "query returned no rows"
    cold_stats = dict(runner.last_query_stats)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        runner.execute(sql)
        times.append(time.perf_counter() - t0)
    warm = sorted(times)[len(times) // 2]  # median
    if breakdown is not None:
        breakdown.update(_breakdown(runner, cold, warm, cold_stats))
        if variant is not None:
            breakdown.update(_literal_variant(runner, variant))
        if prepared is not None:
            breakdown.update(_prepared_variant(runner, prepared))
    return warm


def _prepared_variant(runner, spec):
    """The statement-reuse proof: EXECUTE with perturbed USING values
    (cached plan + warm kernels — what the second-and-later dashboard
    query pays) vs re-submitting the identical statement as plain SQL
    (full parse->plan->optimize per run). prepared_plan_cache_hits >= 1
    and prepared_jit_misses == 0 mean the fast path engaged."""
    name, prepare_sql, warm_using, perturbed_using, resubmit_sql = spec
    try:
        runner.execute(f"PREPARE {name} FROM {prepare_sql}")
        runner.execute(f"EXECUTE {name} USING {warm_using}")
        t0 = time.perf_counter()
        runner.execute(f"EXECUTE {name} USING {perturbed_using}")
        execute_wall = time.perf_counter() - t0
        stats = runner.last_query_stats
        # resubmit baseline: plan cache OFF, else the earlier variant run
        # already cached this exact statement's plan and the "full
        # re-plan" baseline would itself be a cache hit
        runner.session.properties["plan_cache_enabled"] = False
        try:
            t0 = time.perf_counter()
            runner.execute(resubmit_sql)
            resubmit_wall = time.perf_counter() - t0
        finally:
            runner.session.properties.pop("plan_cache_enabled", None)
        return {
            "prepared_execute_wall_s": round(execute_wall, 4),
            "prepared_resubmit_wall_s": round(resubmit_wall, 4),
            "prepared_plan_cache_hits":
                int(stats.get("plan_cache_hits", 0)),
            "prepared_jit_misses": int(stats.get("jit_misses", 0)),
            "prepared_jit_param_hits":
                int(stats.get("jit_param_hits", 0)),
        }
    except Exception as e:  # noqa: BLE001 — a probe failure costs a key,
        return {"prepared_error":            # not the rung
                f"{type(e).__name__}: {str(e)[:120]}"}


def _literal_variant(runner, variant_sql):
    """The parameterized-compilation proof: run the measured query with
    every hoistable constant perturbed. variant_jit_misses == 0 means the
    variant dispatched only warm executables (literal hoisting working);
    variant_warm_wall_s is what a dashboard's next parameter choice
    actually pays."""
    t0 = time.perf_counter()
    runner.execute(variant_sql)
    wall = time.perf_counter() - t0
    stats = runner.last_query_stats
    return {
        "variant_warm_wall_s": round(wall, 4),
        "variant_jit_misses": int(stats.get("jit_misses", 0)),
        "variant_jit_param_hits": int(stats.get("jit_param_hits", 0)),
    }


def _stats_breakdown(stats):
    """The collector-snapshot keys every breakdown object shares."""
    return {
        "planning_s": round(stats.get("planning_s", 0.0), 4),
        "execution_s": round(stats.get("execution_s", 0.0), 4),
        "jit_misses": int(stats.get("jit_misses", 0)),
        "jit_param_hits": int(stats.get("jit_param_hits", 0)),
        "plan_cache_hits": int(stats.get("plan_cache_hits", 0)),
        "output_rows": int(stats.get("output_rows", 0)),
        "output_bytes": int(stats.get("output_bytes", 0)),
        "spilled_bytes": int(stats.get("spilled_bytes", 0)),
        # preemptible sliced execution (round 11): slices the measured
        # run executed, bytes checkpointed for resume, and the measured
        # cancel->unwind wall (0 on an unpreempted run — nonzero here
        # means something canceled/killed the rung, worth seeing)
        "slices_executed": int(stats.get("slices_executed", 0)),
        "checkpoint_bytes": int(stats.get("checkpoint_bytes", 0)),
        "preempt_latency_ms": float(
            stats.get("preempt_latency_ms", 0) or 0),
        # compile-vs-execute accounting (round 13): measured XLA compile
        # wall this run paid (0.0 warm) — cold_wall - warm_wall stops
        # being the only compile signal
        "compile_time_ms": float(stats.get("compile_time_ms", 0) or 0),
        "jit_compiles": int(stats.get("jit_compiles", 0)),
    }


def _breakdown(runner, cold, warm, cold_stats):
    """Compile-vs-execute wall split from the query stats collector
    (obs/stats.py): the cold run pays jit builds + XLA compiles, the warm
    median is steady state, and the collector's phase walls split the
    warm run into planning vs device execution."""
    out = _stats_breakdown(runner.last_query_stats)
    out.update({
        "cold_wall_s": round(cold, 4),
        "warm_wall_s": round(warm, 4),
        "compile_overhead_s": round(max(cold - warm, 0.0), 4),
        "cold_jit_misses": int(cold_stats.get("jit_misses", 0)),
    })
    return out


# the multi-chip rung set: grouped agg (q1), repartitioned group-by +
# joins (q3), 6-way join (q5), wide join + partial agg (q9)
MESH_QUERIES = {"tpch_q1": Q1, "tpch_q3": Q3, "tpch_q5": Q5,
                "tpch_q9": Q9}


def run_mesh(out_path=None) -> None:
    """`bench.py --mesh [OUT.json]`: the multi-chip sharded-execution
    report. Runs q1/q3/q5/q9 through DistributedQueryRunner over the
    device mesh — on TPU the real ICI mesh, elsewhere a forced 8-device
    CPU mesh (re-execs with XLA_FLAGS when needed) — verifies row parity
    against the single-device engine, and emits ONE MULTICHIP json line:
    device_count, per-query walls, fused vs staged exchange counts
    (fused-only == pages never staged through the host), per-chip peak
    bytes, and the node-pool budget + source. Writes the same payload to
    OUT.json when given."""
    platform = _ensure_backend()
    flags = os.environ.get("XLA_FLAGS", "")
    if platform == "cpu" and \
            "--xla_force_host_platform_device_count" not in flags:
        env = dict(os.environ)
        env["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
        argv = [sys.executable, os.path.abspath(__file__), "--mesh"]
        if out_path:
            argv.append(out_path)
        sys.exit(subprocess.run(argv, env=env).returncode)

    payload = {"metric": "multichip_mesh", "device_count": 0,
               "queries": {}, "error": None}
    try:
        import jax

        import trino_tpu
        trino_tpu.enable_persistent_cache()
        from trino_tpu.exec import LocalQueryRunner
        from trino_tpu.exec.distributed import DistributedQueryRunner
        from trino_tpu.exec.memory import NODE_POOL

        schema = os.environ.get("TRINO_TPU_MESH_SCHEMA", "tiny")
        dist = DistributedQueryRunner.tpch(schema)
        local = LocalQueryRunner.tpch(schema)
        n = dist.mesh.n
        payload["device_count"] = n
        payload["backend"] = jax.devices()[0].platform
        if NODE_POOL.limit is None:
            # no measured HBM (CPU dev mesh): give the report window an
            # explicit per-chip budget so peak-vs-budget is a real check,
            # with the same per-device enforcement the TPU path uses
            NODE_POOL.set_limit(int(os.environ.get(
                "TRINO_TPU_MESH_POOL_BYTES", 2 << 30)))
            NODE_POOL.budget_source = "dev-mesh"
            NODE_POOL.enforce_per_device = True
        payload["pool_limit_bytes"] = NODE_POOL.limit or 0
        payload["pool_budget_source"] = NODE_POOL.budget_source
        total_staged = 0
        for tag, sql in MESH_QUERIES.items():
            t0 = time.perf_counter()
            rows = dist.execute(sql).rows
            wall = time.perf_counter() - t0
            st = dist.last_query_stats
            expect = local.execute(sql).rows
            total_staged += int(st.get("exchanges_staged", 0))
            payload["queries"][tag] = {
                "wall_s": round(wall, 4),
                "rows": len(rows),
                "oracle_ok": sorted(map(repr, rows))
                == sorted(map(repr, expect)),
                "exchanges_fused": int(st.get("exchanges_fused", 0)),
                "exchanges_staged": int(st.get("exchanges_staged", 0)),
                "exchange_rows": int(st.get("exchange_rows", 0)),
                "exchange_bytes": int(st.get("exchange_bytes", 0)),
            }
        payload["zero_host_page_exchanges"] = total_staged == 0
        peaks = [NODE_POOL.device_peak.get(i, 0) for i in range(n)]
        payload["per_chip_peak_bytes"] = peaks
        limit = NODE_POOL.limit
        payload["per_chip_peak_within_budget"] = \
            None if not limit else all(p <= limit for p in peaks)
        # real per-device allocator peaks when the backend reports them
        # (TPU HBM); absent on the CPU mesh
        try:
            dev_stats = [d.memory_stats() or {} for d in jax.devices()]
            if any("peak_bytes_in_use" in s for s in dev_stats):
                payload["per_chip_allocator_peak_bytes"] = [
                    int(s.get("peak_bytes_in_use", 0)) for s in dev_stats]
        except Exception:   # noqa: BLE001
            pass
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the line must print
        payload["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    if payload.get("error") is None:
        payload.pop("error")
    line = json.dumps(payload)
    print(line, flush=True)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


def run_lake(out_path=None) -> None:
    """`bench.py --lake [OUT.json]`: the data-plane report. CTAS a
    TPC-H table into a PARTITIONED lake table (round-trip verified
    against the generator connector), then measure the scan ladder the
    lake round exists for:

      cold    first scan — file reads + host->device staging
      warm    repeated scan — scan-cache pages (device), staging = 0
      cached  table-cache scan — HBM-resident columns, staging = 0

    plus a selective pruned scan (files_pruned/row_groups_pruned > 0
    proving partition + zone-map skips) and the INSERT-replay
    exactly-once counter. Always emits its final JSON line."""
    platform = _ensure_backend()
    payload = {"metric": "lake_data_plane", "backend": platform}
    try:
        import trino_tpu
        trino_tpu.enable_persistent_cache()
        from trino_tpu.connector.lake import lake_stats
        from trino_tpu.exec import LocalQueryRunner

        schema = os.environ.get("TRINO_TPU_LAKE_SCHEMA", "tiny")
        runner = LocalQueryRunner.tpch(schema)
        payload["schema"] = schema
        payload["format"] = runner.catalogs.get(
            "lake")._metadata.default_format

        t0 = time.perf_counter()
        runner.execute(
            "CREATE TABLE lake.default.orders_part "
            "WITH (partitioned_by = 'o_orderstatus', "
            "row_group_rows = 65536) AS SELECT * FROM orders")
        payload["ctas_wall_s"] = round(time.perf_counter() - t0, 4)
        src_rows = runner.execute(
            "SELECT count(*) FROM orders").only_value()
        lake_rows = runner.execute(
            "SELECT count(*) FROM lake.default.orders_part").only_value()
        payload["rows"] = int(lake_rows)
        payload["roundtrip_ok"] = bool(lake_rows == src_rows)

        scan = ("SELECT o_orderstatus, count(*), sum(o_totalprice) "
                "FROM lake.default.orders_part GROUP BY o_orderstatus")
        runner.session.set("scan_cache_enabled", True)
        runner.session.set("table_cache_enabled", True)
        runner.session.set("table_cache_min_scans", 2)

        def timed(tag):
            t0 = time.perf_counter()
            rows = runner.execute(scan).rows
            wall = time.perf_counter() - t0
            st = runner.last_query_stats
            payload[f"{tag}_wall_s"] = round(wall, 4)
            payload[f"{tag}_staging_bytes"] = int(
                st.get("scan_staging_bytes", 0))
            payload[f"{tag}_table_cache_hits"] = int(
                st.get("table_cache_hits", 0))
            payload[f"{tag}_scan_cache_hits"] = int(
                st.get("scan_cache_hits", 0))
            return rows

        cold = timed("cold")          # connector read + staging
        warm = timed("warm")          # scan-cache pages + promotion
        cached = timed("cached")      # HBM-resident columns
        payload["scan_parity_ok"] = bool(
            sorted(map(repr, cold)) == sorted(map(repr, warm))
            == sorted(map(repr, cached)))
        payload["cached_zero_staging"] = \
            payload["cached_staging_bytes"] == 0 and \
            payload["cached_table_cache_hits"] > 0

        pruned = runner.execute(
            "SELECT count(*) FROM lake.default.orders_part "
            "WHERE o_orderstatus = 'F' AND o_orderkey < 1000")
        st = runner.last_query_stats
        payload["pruned_scan_rows"] = int(pruned.only_value())
        payload["files_pruned"] = int(st.get("files_pruned", 0))
        payload["row_groups_pruned"] = int(st.get("row_groups_pruned", 0))

        replay_before = lake_stats()["replayed_commits"]
        runner.session.set("fault_injection_rate", 0.5)
        runner.session.set("fault_injection_seed", 1)
        runner.session.set("fault_injection_sites", "fragment")
        runner.session.set("retry_policy", "QUERY")
        runner.session.set("retry_attempts", 5)
        runner.execute("INSERT INTO lake.default.orders_part "
                       "SELECT * FROM orders WHERE o_orderkey < 100")
        insert_retries = int(runner.last_query_stats.get("retries", 0))
        runner.session.set("fault_injection_rate", 0.0)
        extra = runner.execute("SELECT count(*) FROM orders "
                               "WHERE o_orderkey < 100").only_value()
        after = runner.execute(
            "SELECT count(*) FROM lake.default.orders_part").only_value()
        payload["insert_retries"] = insert_retries
        payload["insert_replays"] = \
            lake_stats()["replayed_commits"] - replay_before
        payload["insert_exactly_once"] = bool(
            after == src_rows + extra)
        payload["lake_counters"] = lake_stats()
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the line must print
        payload["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    line = json.dumps(payload)
    print(line, flush=True)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


def run_mv(out_path=None) -> None:
    """`bench.py --mv [OUT.json]`: the update-on-write cache-tier
    report. Two instruments over one lake table and one incremental
    materialized view:

      refresh ratio   after a 1% append, DELTA refresh (merge only the
                      manifest diff into stored partial states) vs a
                      forced FULL recompute — acceptance: delta wall
                      <= 10% of full wall
      serving trickle a closed loop of 8 MV-rewritable aggregate
                      queries under a 1-write-per-cycle INSERT trickle:
                      update-on-write (refresh republishes the cached
                      results) vs the invalidate-on-write baseline
                      (every write floods the result cache, every
                      query recomputes) — acceptance: >= 5x QPS with
                      ZERO stale answers (every served row set equals
                      the post-write oracle)

    Always emits its final JSON line."""
    platform = _ensure_backend()
    payload = {"metric": "mv_update_on_write", "backend": platform}
    try:
        import trino_tpu
        trino_tpu.enable_persistent_cache()
        from trino_tpu.exec import LocalQueryRunner

        runner = LocalQueryRunner.tpch("tiny")
        # ~240k rows: doubling INSERTs over a 15k-row CTAS seed
        runner.execute(
            "CREATE TABLE lake.default.big AS SELECT o_orderstatus AS k,"
            " o_totalprice AS v, o_orderkey AS n FROM orders")
        for _ in range(4):
            runner.execute("INSERT INTO lake.default.big "
                           "SELECT k, v, n FROM lake.default.big")
        base_rows = runner.execute(
            "SELECT count(*) FROM lake.default.big").only_value()
        payload["base_rows"] = int(base_rows)
        delta_rows = max(1, base_rows // 100)
        payload["delta_rows"] = int(delta_rows)

        def delta_insert_sql(rows):
            return ("INSERT INTO lake.default.big "
                    "SELECT k, v, n FROM lake.default.big "
                    f"LIMIT {rows}")

        delta_insert = delta_insert_sql(delta_rows)

        runner.execute(
            "CREATE MATERIALIZED VIEW lake.default.mv_big AS "
            "SELECT k, sum(v) AS s, count(*) AS c, min(v) AS lo, "
            "max(v) AS hi, avg(v) AS a "
            "FROM lake.default.big GROUP BY k")
        refresh = "REFRESH MATERIALIZED VIEW lake.default.mv_big"
        stats = runner._mv.stats[("lake", "default", "mv_big")]

        def timed_refresh(mode, rows=delta_rows):
            runner.execute(delta_insert_sql(rows))
            runner.session.set("mv_refresh_mode", mode)
            t0 = time.perf_counter()
            runner.execute(refresh)
            return time.perf_counter() - t0

        timed_refresh("AUTO")           # warm the delta-merge kernels
        delta_wall = timed_refresh("AUTO")
        delta10_wall = timed_refresh("AUTO", rows=base_rows // 10)
        timed_refresh("FULL")           # warm the full-recompute path
        full_wall = timed_refresh("FULL")
        assert stats["refreshes_delta"] >= 3, stats
        payload["delta_refresh_wall_s"] = round(delta_wall, 4)
        payload["delta10_refresh_wall_s"] = round(delta10_wall, 4)
        payload["full_refresh_wall_s"] = round(full_wall, 4)
        payload["refresh_ratio"] = round(delta_wall / full_wall, 4)
        payload["refresh_ratio_10pct"] = round(
            delta10_wall / full_wall, 4)
        payload["refresh_ratio_ok"] = bool(
            delta_wall <= 0.10 * full_wall)

        # ---- serving under a write trickle --------------------------
        queries = [
            "SELECT k, sum(v) AS s FROM lake.default.big GROUP BY k "
            "ORDER BY k",
            "SELECT k, count(*) AS c FROM lake.default.big GROUP BY k "
            "ORDER BY k",
            "SELECT k, min(v) AS lo FROM lake.default.big GROUP BY k "
            "ORDER BY k",
            "SELECT k, max(v) AS hi FROM lake.default.big GROUP BY k "
            "ORDER BY k",
            "SELECT k, avg(v) AS a FROM lake.default.big GROUP BY k "
            "ORDER BY k",
            "SELECT k, sum(v) AS s, count(*) AS c FROM lake.default.big "
            "GROUP BY k ORDER BY k",
            "SELECT k, min(v) AS lo, max(v) AS hi FROM lake.default.big "
            "GROUP BY k ORDER BY k",
            "SELECT k, sum(v) AS s, avg(v) AS a FROM lake.default.big "
            "GROUP BY k ORDER BY s DESC",
        ]

        def oracle_answers():
            runner.session.set("mv_rewrite_enabled", False)
            runner.session.set("result_cache_enabled", False)
            out = [runner.execute(q).rows for q in queries]
            runner.session.set("result_cache_enabled", True)
            return out

        def trickle(update_on_write, cycles=3, window_s=1.0):
            runner.session.set("result_cache_enabled", True)
            runner.session.set("mv_rewrite_enabled", update_on_write)
            for q in queries:            # seed the cache tier
                runner.execute(q)
            served = 0
            stale = 0
            wall = 0.0
            for _ in range(cycles):
                t0 = time.perf_counter()
                runner.execute(delta_insert)
                if update_on_write:
                    runner.session.set("mv_refresh_mode", "AUTO")
                    runner.session.set("mv_rewrite_enabled", True)
                    runner.execute(refresh)
                answers = {}
                i = 0
                while time.perf_counter() - t0 < window_s:
                    q = queries[i % len(queries)]
                    answers.setdefault(q, []).append(
                        runner.execute(q).rows)
                    served += 1
                    i += 1
                wall += time.perf_counter() - t0
                expected = oracle_answers()
                runner.session.set(
                    "mv_rewrite_enabled", update_on_write)
                for q, exp in zip(queries, expected):
                    for got in answers.get(q, ()):
                        if got != exp:
                            stale += 1
            return served / wall, stale

        baseline_qps, baseline_stale = trickle(update_on_write=False)
        uow_qps, uow_stale = trickle(update_on_write=True)
        payload["baseline_qps"] = round(baseline_qps, 2)
        payload["update_on_write_qps"] = round(uow_qps, 2)
        payload["qps_speedup"] = round(uow_qps / baseline_qps, 2)
        payload["qps_speedup_ok"] = bool(uow_qps >= 5 * baseline_qps)
        payload["stale_answers"] = int(uow_stale)
        payload["baseline_stale_answers"] = int(baseline_stale)
        payload["zero_stale"] = bool(uow_stale == 0)
        payload["mv_stats"] = dict(stats)
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the line must print
        payload["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    line = json.dumps(payload)
    print(line, flush=True)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


def run_scrub(out_path=None) -> None:
    """`bench.py --scrub [OUT.json]`: the data-integrity report.

      verify overhead   warm lake scans at lake_verify_checksums off /
                        row_group (default) / file — the acceptance bar
                        is row_group overhead <= 5% over off
      fsck wall         deep pointer->manifest->files->row-groups walk
                        over a multi-hundred-file lake table
      detection latency flip one byte on disk, time to the classified
                        LAKE_DATA_CORRUPTION error (never wrong rows)

    Always emits its final JSON line."""
    platform = _ensure_backend()
    payload = {"metric": "lake_scrub", "backend": platform}
    try:
        import glob

        import trino_tpu
        trino_tpu.enable_persistent_cache()
        from trino_tpu.connector.lake import clear_quarantine, lake_stats
        from trino_tpu.errors import LakeDataCorruptionError
        from trino_tpu.exec import LocalQueryRunner

        schema = os.environ.get("TRINO_TPU_LAKE_SCHEMA", "tiny")
        reps = int(os.environ.get("TRINO_TPU_SCRUB_REPS", "15"))
        n_files = int(os.environ.get("TRINO_TPU_SCRUB_FILES", "240"))
        runner = LocalQueryRunner.tpch(schema)
        payload["schema"] = schema
        lake_dir = runner.catalogs.get("lake")._metadata.base_dir

        runner.execute(
            "CREATE TABLE lake.default.li WITH (row_group_rows = 8192) "
            "AS SELECT * FROM lineitem")
        scan = ("SELECT sum(l_extendedprice), sum(l_quantity), "
                "count(*) FROM lake.default.li WHERE l_quantity > 10")

        # --- verify overhead: same warm scan, three verification
        # levels. "first" clears the verified-content ledger every rep
        # (every digest re-hashed); plain warm reps pay the ledger's
        # steady state — the acceptance number at the row_group default.
        from trino_tpu.connector.lake import clear_verified

        def level_wall(level, first=False):
            runner.session.set("lake_verify_checksums", level)
            runner.execute(scan)            # warm (jit + page cache)
            walls = []
            for _ in range(reps):
                if first:
                    clear_verified()
                t0 = time.perf_counter()
                runner.execute(scan)
                walls.append(time.perf_counter() - t0)
            # best-of-N: the noise floor is the comparable number —
            # scheduler jitter at ms scale would otherwise swamp a
            # zero-cost ledger hit
            return min(walls)

        off = level_wall("off")
        row_group = level_wall("row_group")
        file_level = level_wall("file")
        first_rg = level_wall("row_group", first=True)
        payload["scan_wall_off_s"] = round(off, 5)
        payload["scan_wall_row_group_s"] = round(row_group, 5)
        payload["scan_wall_file_s"] = round(file_level, 5)
        payload["scan_wall_first_verify_s"] = round(first_rg, 5)
        payload["verify_overhead_row_group"] = round(
            (row_group - off) / off, 4)
        payload["verify_overhead_file"] = round(
            (file_level - off) / off, 4)
        payload["verify_overhead_first_scan"] = round(
            (first_rg - off) / off, 4)
        payload["verify_overhead_ok"] = bool(
            payload["verify_overhead_row_group"] <= 0.05)
        runner.session.set("lake_verify_checksums", "row_group")

        # --- fsck wall over a multi-hundred-file table (one file per
        # commit: the worst-case manifest/file fan-out, not row volume)
        runner.execute("CREATE TABLE lake.default.many (x bigint, "
                       "y double)")
        t0 = time.perf_counter()
        for i in range(n_files):
            runner.execute(f"INSERT INTO lake.default.many VALUES "
                           f"({i}, {i}.5), ({i + 1}, {i}.25)")
        payload["ingest_wall_s"] = round(time.perf_counter() - t0, 4)
        payload["lake_files"] = sum(
            len(glob.glob(os.path.join(t, "data", "*")))
            for t in glob.glob(os.path.join(lake_dir, "default", "*")))
        t0 = time.perf_counter()
        report = runner.lake_fsck()
        payload["fsck_wall_s"] = round(time.perf_counter() - t0, 4)
        payload["fsck_ok"] = bool(report["ok"])
        payload["fsck_tables"] = int(report["tables_checked"])

        # --- detection latency: one flipped byte on disk -> classified
        runner.execute("CREATE TABLE lake.default.det AS "
                       "SELECT * FROM nation")
        runner.execute("SELECT count(*) FROM lake.default.det")
        path = sorted(glob.glob(os.path.join(
            lake_dir, "default", "det", "data", "*")))[0]
        with open(path, "r+b") as fh:     # scatter flips: whatever the
            data = bytearray(fh.read())   # scan decodes is affected
            for pos in range(16, len(data), 128):
                data[pos] ^= 0xFF
            fh.seek(0)
            fh.write(data)
        clear_quarantine()
        t0 = time.perf_counter()
        try:
            runner.execute("SELECT count(n_nationkey) "
                           "FROM lake.default.det")
            payload["detection_classified"] = False   # silent wrong rows
        except LakeDataCorruptionError:
            payload["detection_classified"] = True
        payload["detection_latency_s"] = round(
            time.perf_counter() - t0, 5)
        payload["lake_counters"] = lake_stats()
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the line must print
        payload["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    line = json.dumps(payload)
    print(line, flush=True)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


def run_qps(out_path=None, workers=None) -> None:
    """`bench.py --qps [OUT.json] [--workers N1,N2,...]`: the serving
    tier's QPS instrument. Without `--workers`, the PR-7 single-process
    closed loop (trino_tpu/serve/bench_serve.py). With `--workers`, the
    FLEET scaling curve (trino_tpu/fleet/bench_fleet.py): one rung per
    worker count (0 = single-process baseline), subprocess load
    generators, a cache-MISS pass proving the dispatch path doesn't
    regress behind the proxy hop, and a mid-bench rolling restart
    proving zero dropped queries. Like the main bench, the final JSON
    line ALWAYS prints: a failure lands in an `error` field instead of
    a bare nonzero exit with nothing parseable."""
    platform = _ensure_backend()
    if workers is None and os.environ.get("TRINO_TPU_QPS_WORKERS"):
        raw_workers = os.environ["TRINO_TPU_QPS_WORKERS"]
        try:
            workers = [int(x) for x in raw_workers.split(",")]
        except ValueError:
            # the contract: the final JSON line ALWAYS prints
            line = json.dumps({
                "metric": "fleet_qps", "backend": platform,
                "error": f"bad TRINO_TPU_QPS_WORKERS value "
                         f"{raw_workers!r} (want e.g. '0,1,2,4,8')"})
            print(line, flush=True)
            if out_path:
                with open(out_path, "w") as f:
                    f.write(line + "\n")
            return
    # one env read, mode-specific defaults: the fleet curve runs 5
    # rungs + a miss pass + the restart pass, so its per-rung window is
    # shorter than the single-process loop's
    clients = int(os.environ.get("TRINO_TPU_QPS_CLIENTS", 8))
    env_duration = os.environ.get("TRINO_TPU_QPS_DURATION_S")
    if workers is not None:
        from trino_tpu.fleet.bench_fleet import run_fleet_qps
        metric = "fleet_qps"
        bench = run_fleet_qps
        kwargs = {"worker_counts": workers, "client_procs": clients,
                  "duration_s": float(env_duration) if env_duration
                  else 6.0}
    else:
        from trino_tpu.serve.bench_serve import run_qps_bench
        metric = "serve_qps"
        bench = run_qps_bench
        kwargs = {"clients": clients,
                  "duration_s": float(env_duration) if env_duration
                  else 8.0}
    payload = {"metric": metric, "backend": platform}
    try:
        payload.update(bench(**kwargs))
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the line must print
        payload["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    line = json.dumps(payload)
    print(line, flush=True)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


def run_chaos_fleet(out_path=None) -> None:
    """`bench.py --chaos-fleet [OUT.json]`: the process-level fault
    matrix (trino_tpu/fleet/bench_fleet.py run_chaos_fleet). One phase
    per process class against a live fleet: kill -9 the ENGINE under
    load (shared-tier hits must stay fully available, misses classify
    as retryable ENGINE_UNAVAILABLE, the supervisor restores an active
    generation), kill -9 a WORKER (siblings hold the shared port, the
    headcount respawns), then a PLANNED `engine_restart()` under a
    closed loop of cache misses (the SCM_RIGHTS listener handoff must
    land errors == 0). The final JSON line ALWAYS prints; `chaos_clean`
    is the single acceptance bit."""
    platform = _ensure_backend()
    payload = {"metric": "chaos_fleet", "backend": platform}
    try:
        from trino_tpu.fleet.bench_fleet import run_chaos_fleet as _run
        payload.update(_run())
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the line must print
        payload["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    line = json.dumps(payload)
    print(line, flush=True)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


def run_preempt(out_path=None) -> None:
    """`bench.py --preempt [OUT.json]`: the DELETE->executor-freed
    smoke. Starts a long SF1 lineitem scan on a worker thread, cancels
    it mid-flight through the SAME shared cancel event the server's
    DELETE handler sets, and reports the measured cancel-to-freed wall
    plus the slice counters of the preempted run. The acceptance shape:
    `cancel_to_free_ms` is bounded by ~one slice, orders of magnitude
    below `scan_wall_s_estimate` (what the scan had left). Like every
    bench mode, the final JSON line ALWAYS prints — failures land in an
    `error` field."""
    import threading
    platform = _ensure_backend()
    payload = {"metric": "preempt_latency", "backend": platform}
    try:
        import trino_tpu
        trino_tpu.enable_persistent_cache()
        from trino_tpu.errors import QueryCanceledError
        from trino_tpu.exec import LocalQueryRunner
        from trino_tpu.exec.memory import NODE_POOL

        schema = os.environ.get("TRINO_TPU_PREEMPT_SCHEMA", "sf1")
        runner = LocalQueryRunner.tpch(schema)
        long_scan = ("SELECT count(*), sum(l_extendedprice * "
                     "(1 - l_discount)) FROM lineitem "
                     "WHERE l_quantity >= 0")
        # warm run: compiles + stages the table, and tells us what the
        # full scan costs (the denominator of the latency claim)
        t0 = time.perf_counter()
        runner.execute(long_scan)
        full_wall = time.perf_counter() - t0
        payload["scan_wall_s_estimate"] = round(full_wall, 3)
        payload["slice_target_rows"] = int(
            runner.session.get("slice_target_rows"))

        from trino_tpu.exec.deadline import CancelEvent
        outcome = {}
        cancel_event = CancelEvent()

        def worker():
            try:
                runner.execute(long_scan, query_id="bench_preempt",
                               cancel_event=cancel_event)
                outcome["state"] = "finished-before-cancel"
            except QueryCanceledError:
                outcome["state"] = "canceled"
            except BaseException as e:  # noqa: BLE001
                outcome["state"] = f"error: {type(e).__name__}: {e}"
            outcome["done_at"] = time.monotonic()

        th = threading.Thread(target=worker)
        th.start()
        # cancel partway into the warm wall so the scan is mid-flight
        time.sleep(max(min(full_wall * 0.3, 2.0), 0.02))
        cancel_event.cancel()       # the DELETE handler's exact path
        th.join(timeout=max(4 * full_wall, 60))
        stats = runner.last_query_stats
        canceled = outcome.get("state") == "canceled"
        payload.update({
            "outcome": outcome.get("state", "hung"),
            # meaningful only when the cancel actually preempted the
            # scan (a too-fast scan reports its outcome and no latency)
            "cancel_to_free_ms": round(
                (outcome["done_at"] - cancel_event.cancelled_at) * 1000,
                1) if canceled and "done_at" in outcome else None,
            "preempt_latency_ms": float(
                stats.get("preempt_latency_ms", 0) or 0),
            "slices_executed": int(stats.get("slices_executed", 0)),
            "checkpoint_bytes": int(stats.get("checkpoint_bytes", 0)),
            "pool_reserved_after": NODE_POOL.reserved,
        })
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the line must print
        payload["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    line = json.dumps(payload)
    print(line, flush=True)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


def run_join_micro(out_path=None) -> None:
    """`bench.py --join-micro [OUT.json]`: matmul-vs-gather head-to-head
    (ROADMAP item 1 / ops/join_mxu.py). Builds synthetic probe/build
    tables from TPC-H data at several density/NDV rungs plus the
    many-to-many AGGREGATING-join rung (the TPC-DS q64/q72 shape: match
    multiplicities feed SUM/COUNT without materializing the cross
    product), and times each rung with the MXU router enabled vs pinned
    off. Per rung: warm walls, probe rows/s both ways, the speedup, the
    mxu_joins/mxu_flops counters, the cold run's XLA cost-model compile
    flops (nonzero matmul flops = the MXU kernels really compiled), and
    a row-parity check. The final JSON line ALWAYS prints; failures
    land in an `error` field. TPU re-run is noted as blocked per
    ROADMAP item 5 — these are CPU numbers."""
    platform = _ensure_backend()
    schema = os.environ.get("TRINO_TPU_JOIN_MICRO_SCHEMA", "sf1")
    payload = {"metric": "join_micro", "backend": platform,
               "schema": schema,
               "tpu_note": "CPU numbers; TPU re-run blocked on device "
                           "access (ROADMAP item 5)"}
    try:
        import trino_tpu
        trino_tpu.enable_persistent_cache()
        from trino_tpu.exec import LocalQueryRunner

        probe_rows = int(os.environ.get("TRINO_TPU_JOIN_MICRO_ROWS",
                                        1 << 20))
        runner = LocalQueryRunner.tpch(schema)
        runner.execute(
            "CREATE TABLE memory.default.jm_probe AS "
            "SELECT l_partkey AS kp, l_orderkey % 2048 AS km, "
            "l_orderkey % 64 AS g, l_quantity AS v "
            f"FROM lineitem LIMIT {probe_rows}")
        n_probe = runner.execute(
            "SELECT count(*) FROM memory.default.jm_probe").rows[0][0]
        runner.execute(
            "CREATE TABLE memory.default.jm_build_m2m AS "
            "SELECT l_orderkey % 2048 AS k, l_extendedprice AS w "
            "FROM lineitem LIMIT 32768")
        runner.execute(
            "CREATE TABLE memory.default.jm_build_u4k AS "
            "SELECT p_partkey AS k, p_retailprice AS w FROM part "
            "WHERE p_partkey <= 4000")
        runner.execute(
            "CREATE TABLE memory.default.jm_build_u512 AS "
            "SELECT p_partkey AS k, p_retailprice AS w FROM part "
            "WHERE p_partkey <= 512")
        runner.execute(
            "CREATE TABLE memory.default.jm_build_sparse AS "
            "SELECT p_partkey AS k, p_retailprice AS w FROM part "
            "WHERE p_partkey <= 4000 AND p_partkey % 64 = 0")
        # (name, build table, sql) — the non-fused rungs aggregate a
        # COMPUTED expression so the join-project probe kernel itself
        # is what runs; the m2m rung is the fused aggregating join
        rungs = [
            ("dense_unique_ndv4k", "jm_build_u4k",
             "SELECT count(*), max(v + w) FROM memory.default.jm_probe "
             "p, memory.default.jm_build_u4k b WHERE p.kp = b.k"),
            ("dense_unique_ndv512", "jm_build_u512",
             "SELECT count(*), max(v + w) FROM memory.default.jm_probe "
             "p, memory.default.jm_build_u512 b WHERE p.kp = b.k"),
            ("sparse_density_1_64", "jm_build_sparse",
             "SELECT count(*), max(v + w) FROM memory.default.jm_probe "
             "p, memory.default.jm_build_sparse b WHERE p.kp = b.k"),
            ("m2m_aggregating", "jm_build_m2m",
             "SELECT g, count(*) c, sum(v) sv, sum(w) sw "
             "FROM memory.default.jm_probe p, "
             "memory.default.jm_build_m2m b WHERE p.km = b.k "
             "GROUP BY g ORDER BY g"),
        ]
        out_rungs = []
        for name, build_table, sql in rungs:
            info = runner.execute(
                f"SELECT count(*), count(DISTINCT k), min(k), max(k) "
                f"FROM memory.default.{build_table}").rows[0]
            brows, ndv, kmin, kmax = (int(x) for x in info)
            span = kmax - kmin + 1 if kmax >= kmin else 0
            rung = {"name": name, "build_rows": brows, "ndv": ndv,
                    "span": span,
                    "density": round(ndv / span, 4) if span else 0.0,
                    "duplication": round(brows / max(ndv, 1), 2)}

            def timed(enabled):
                runner.execute("SET SESSION mxu_join_enabled = "
                               + ("true" if enabled else "false"))
                t0 = time.perf_counter()
                res = runner.execute(sql)
                cold_wall = time.perf_counter() - t0
                cold = dict(runner.last_query_stats)
                t0 = time.perf_counter()
                res = runner.execute(sql)
                warm_wall = time.perf_counter() - t0
                warm = dict(runner.last_query_stats)
                return res.rows, cold_wall, warm_wall, cold, warm

            mxu_rows, mxu_cold, mxu_wall, mxu_cstats, mxu_stats = \
                timed(True)
            g_rows, g_cold, g_wall, _g_c, _g_w = timed(False)
            rung.update({
                "routed": "mxu-matmul"
                          if mxu_stats.get("mxu_joins", 0) else "gather",
                "mxu_warm_wall_s": round(mxu_wall, 4),
                "gather_warm_wall_s": round(g_wall, 4),
                "speedup": round(g_wall / max(mxu_wall, 1e-9), 3),
                "probe_rows_s_mxu": round(n_probe / max(mxu_wall, 1e-9)),
                "probe_rows_s_gather": round(
                    n_probe / max(g_wall, 1e-9)),
                "mxu_joins": int(mxu_stats.get("mxu_joins", 0)),
                "mxu_flops": float(mxu_stats.get("mxu_flops", 0)),
                "compile_flops_cold": float(
                    mxu_cstats.get("estimated_flops", 0)),
                "rows_match": sorted(map(str, mxu_rows))
                              == sorted(map(str, g_rows)),
            })
            out_rungs.append(rung)
        payload["probe_rows"] = int(n_probe)
        payload["rungs"] = out_rungs
        # per-operator attribution over the m2m rung: the measured
        # device wall apportions by XLA cost analysis (obs/profiler);
        # the query-level counters carry the matmul flops proof
        runner.execute("SET SESSION mxu_join_enabled = true")
        runner.execute("SET SESSION collect_operator_stats = true")
        runner.execute(rungs[-1][2])
        st = runner.last_query_stats
        ops = sorted(st.get("operators", []),
                     key=lambda o: -o.get("device_ms", 0))[:4]
        payload["m2m_attribution"] = {
            "mxu_joins": int(st.get("mxu_joins", 0)),
            "mxu_flops": float(st.get("mxu_flops", 0)),
            "top_operators_by_device_ms": [
                {"name": o["name"],
                 "device_ms": o.get("device_ms", 0)} for o in ops],
        }
        m2m = out_rungs[-1]
        payload["m2m_speedup"] = m2m["speedup"]
        payload["mxu_beats_gather"] = bool(
            m2m["routed"] == "mxu-matmul" and m2m["speedup"] > 1.0
            and m2m["mxu_flops"] > 0)
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the line must print
        payload["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    line = json.dumps(payload)
    print(line, flush=True)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


Q18_LADDER = """
SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
       sum(l_quantity)
FROM customer, orders, lineitem
WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem
                     GROUP BY l_orderkey
                     HAVING sum(l_quantity) > 300)
  AND c_custkey = o_custkey AND o_orderkey = l_orderkey
GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
ORDER BY o_totalprice DESC, o_orderdate LIMIT 100
"""

# a deliberately skewed duplicate-key join: both sides of the self-join
# carry ~4 rows per orderkey, so the build is never unique — the shape
# that exercises the partitioned hybrid join's recursion/heavy paths
# (TPC-H's own joins are all FK->PK unique builds)
SKEW_LADDER = """
SELECT count(*), sum(l2.l_extendedprice)
FROM lineitem l1 JOIN lineitem l2 ON l1.l_orderkey = l2.l_orderkey
"""

# NDV == rows: partial aggregation collapses NOTHING, so the adaptive
# controller must downgrade (full -> shrunken -> bypass) — q9/q18's own
# GROUP BYs genuinely reduce, which the consistent raw-row ratio now
# correctly keeps in full mode
HIGH_NDV_LADDER = """
SELECT l_orderkey, l_linenumber, sum(l_extendedprice), avg(l_quantity)
FROM lineitem GROUP BY l_orderkey, l_linenumber
"""

LADDER_FRACTIONS = (1.0, 0.5, 0.25, 0.125)
LADDER_COUNTERS = ("spilled_bytes", "agg_mode_downgrades",
                   "agg_mode_upgrades", "agg_recursions",
                   "join_recursions", "heavy_key_splits",
                   "spill_fallbacks", "retries")


def run_profile(out_path=None) -> None:
    """`bench.py --profile [OUT.json]`: the device-time-truth report
    (round 13, obs/profiler.py). Runs q1/q6/q9 with operator-level
    collection ON — which since round 13 executes the SAME plan and the
    SAME fused executables as the plain query (no chain splitting; the
    `stats_jit_misses` field proves it: a warm instrumented run
    dispatches zero new kernels) — and reports each query's
    device/compile/host wall split plus its top-5 operators by
    cost-model-apportioned device time. The cold run's compile wall is
    measured at the jit cache's AOT compile sites, not inferred from a
    cold-vs-warm delta. The final JSON line ALWAYS prints — failures
    land in `error` fields, never a silent rc=1."""
    platform = _ensure_backend()
    payload = {"metric": "profile", "backend": platform, "queries": {}}
    try:
        import trino_tpu
        trino_tpu.enable_persistent_cache()
        from trino_tpu.exec import LocalQueryRunner

        schema = os.environ.get(
            "TRINO_TPU_PROFILE_SCHEMA",
            "tiny" if platform == "cpu" else "sf1")
        payload["schema"] = schema
        runner = LocalQueryRunner.tpch(schema)
        runner.session.set("collect_operator_stats", True)
        for tag, sql in (("tpch_q1", Q1), ("tpch_q6", Q6),
                         ("tpch_q9", Q9)):
            qinfo = {}
            payload["queries"][tag] = qinfo
            try:
                t0 = time.perf_counter()
                runner.execute(sql)
                qinfo["cold_wall_s"] = round(time.perf_counter() - t0, 4)
                cold = dict(runner.last_query_stats)
                t0 = time.perf_counter()
                runner.execute(sql)
                qinfo["warm_wall_s"] = round(time.perf_counter() - t0, 4)
                warm = dict(runner.last_query_stats)
                qinfo["cold_compile_time_ms"] = cold.get(
                    "compile_time_ms", 0.0)
                qinfo["cold_jit_compiles"] = cold.get("jit_compiles", 0)
                qinfo["device_time_ms"] = warm.get("device_time_ms", 0.0)
                qinfo["compile_time_ms"] = warm.get("compile_time_ms",
                                                    0.0)
                qinfo["host_time_ms"] = warm.get("host_time_ms", 0.0)
                qinfo["planning_ms"] = round(
                    warm.get("planning_s", 0.0) * 1000, 3)
                # the no-splitting proof: the warm instrumented run must
                # dispatch only executables the cold run compiled
                qinfo["stats_jit_misses"] = warm.get("jit_misses", 0)
                ops = sorted(warm.get("operators", []),
                             key=lambda o: -o.get("device_ms", 0.0))
                qinfo["top_operators_by_device_ms"] = [
                    {"name": o["name"],
                     "device_ms": o.get("device_ms", 0.0),
                     "wall_ms": o.get("wall_ms", 0.0),
                     "output_rows": o.get("output_rows", 0)}
                    for o in ops[:5]]
                dev_sum = sum(o.get("device_ms", 0.0)
                              for o in warm.get("operators", []))
                qinfo["operator_device_ms_sum"] = round(dev_sum, 3)
                # attribution closes: per-operator device shares sum to
                # the measured chain walls (within float rounding)
                qinfo["attribution_closes"] = abs(
                    dev_sum - warm.get("device_time_ms", 0.0)) < 1.0
            except BaseException as e:  # noqa: BLE001
                qinfo["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    except BaseException as e:  # noqa: BLE001 — the JSON line must print
        payload["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    line = json.dumps(payload)
    print(line, flush=True)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


def run_memory_ladder(out_path=None) -> None:
    """`bench.py --memory-ladder [OUT.json]`: the no-cliff proof. Runs
    q9 / q18 / a skewed self-join under a shrinking forced node pool
    (1x, 1/2, 1/4, 1/8 of each query's measured working set) with
    retry_policy=QUERY, so an over-pool attempt is killed by the
    low-memory killer and the degrade re-run — inheriting the failed
    attempt's adaptive state — finishes under the spill ladder. Emits
    per-rung wall, spilled bytes, and the adaptive counters, plus a
    `no_cliff` boolean: every rung completed (no OOM, no unbounded
    recursion) and wall degrades smoothly (no rung blows up past
    NO_CLIFF_STEP x its predecessor). The final JSON line ALWAYS
    prints — failures land in `error` fields, never a silent rc=1."""
    platform = _ensure_backend()
    payload = {"metric": "memory_ladder", "backend": platform,
               "queries": {}}
    no_cliff = True
    step_tol = float(os.environ.get("TRINO_TPU_LADDER_STEP_TOL", 8.0))
    try:
        import trino_tpu
        trino_tpu.enable_persistent_cache()
        from trino_tpu.exec import LocalQueryRunner
        from trino_tpu.exec.memory import NODE_POOL
        from trino_tpu.exec.query_tracker import TRACKER

        schema = os.environ.get("TRINO_TPU_LADDER_SCHEMA", "tiny")
        payload["schema"] = schema
        runner = LocalQueryRunner.tpch(schema)
        # small pages so buffers/compactions actually stream (one giant
        # fused scan page would hide every adaptive boundary), QUERY
        # retry so the killer's victim gets its spill-forced degrade run
        for k, v in (("page_capacity", 4096),
                     ("scan_page_capacity", 8192),
                     ("spill_partition_count", 8),
                     ("retry_policy", "QUERY")):
            runner.session.set(k, v)

        ladder = {"tpch_q9": Q9, "tpch_q18": Q18_LADDER,
                  "skew_join": SKEW_LADDER,
                  "high_ndv_agg": HIGH_NDV_LADDER}
        for tag, sql in ladder.items():
            qinfo = {"rungs": []}
            payload["queries"][tag] = qinfo
            # working set = the unconstrained run's peak pool
            # reservation (also the warm-compile run)
            wsid = f"ladder_ws_{tag}"
            try:
                t0 = time.perf_counter()
                runner.execute(sql, query_id=wsid)
                base_wall = time.perf_counter() - t0
            except BaseException as e:  # noqa: BLE001
                qinfo["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                no_cliff = False
                continue
            peak = max((q.pool_peak_bytes for q in TRACKER.list()
                        if q.query_id == wsid), default=0)
            ws = max(int(peak), 1 << 20)
            qinfo["working_set_bytes"] = ws
            qinfo["unconstrained_wall_s"] = round(base_wall, 4)
            # warm the spill/recursion kernels at the TIGHTEST rung's
            # config (untimed): the rung walls must measure the adaptive
            # ladder's steady state, not first-spill XLA compiles
            try:
                tight = max(int(ws * LADDER_FRACTIONS[-1]) // 4, 1 << 16)
                for prop in ("join_spill_threshold_bytes",
                             "agg_spill_threshold_bytes",
                             "sort_spill_threshold_bytes"):
                    runner.session.set(prop, tight)
                runner.execute(sql)
            except BaseException:  # noqa: BLE001 — warming is best-effort
                pass
            finally:
                for prop in ("join_spill_threshold_bytes",
                             "agg_spill_threshold_bytes",
                             "sort_spill_threshold_bytes"):
                    runner.session.properties.pop(prop, None)
            prev_wall = None
            for frac in LADDER_FRACTIONS:
                limit = max(int(ws * frac), 1 << 18)
                rung = {"fraction": frac, "pool_limit_bytes": limit}
                qinfo["rungs"].append(rung)
                # the query ledger tracks the pool (mid-collect overflow
                # hands builds to the streaming partitioned join) and
                # the spill thresholds shrink proportionally so blocking
                # operators flush instead of materializing over the rung
                runner.session.set("query_max_memory", limit)
                spill_t = max(limit // 4, 1 << 16)
                for prop in ("join_spill_threshold_bytes",
                             "agg_spill_threshold_bytes",
                             "sort_spill_threshold_bytes"):
                    runner.session.set(prop, spill_t)
                try:
                    with NODE_POOL.limited(limit):
                        t0 = time.perf_counter()
                        runner.execute(sql)
                        rung["wall_s"] = round(
                            time.perf_counter() - t0, 4)
                except KeyboardInterrupt:
                    raise
                except BaseException as e:  # noqa: BLE001
                    rung["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                    no_cliff = False
                    continue
                finally:
                    for prop in ("query_max_memory",
                                 "join_spill_threshold_bytes",
                                 "agg_spill_threshold_bytes",
                                 "sort_spill_threshold_bytes"):
                        runner.session.properties.pop(prop, None)
                stats = runner.last_query_stats
                for key in LADDER_COUNTERS:
                    rung[key] = int(stats.get(key, 0))
                if prev_wall is not None and \
                        rung["wall_s"] > step_tol * max(prev_wall, 1e-3):
                    # a cliff: one halving of memory blew the wall up
                    # by more than the tolerated degradation step
                    rung["cliff"] = True
                    no_cliff = False
                prev_wall = rung["wall_s"]
            totals = {k: sum(r.get(k, 0) for r in qinfo["rungs"])
                      for k in LADDER_COUNTERS}
            qinfo["totals"] = totals
        all_counters = {
            k: sum(q.get("totals", {}).get(k, 0)
                   for q in payload["queries"].values())
            for k in LADDER_COUNTERS}
        payload["counters"] = all_counters
        payload["adaptive_paths_fired"] = bool(
            all_counters.get("agg_mode_downgrades", 0)
            and all_counters.get("join_recursions", 0)
            and all_counters.get("spilled_bytes", 0))
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the line must print
        payload["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        no_cliff = False
    payload["no_cliff"] = no_cliff
    line = json.dumps(payload)
    print(line, flush=True)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


def main():
    """Always emits exactly one final JSON line: a backend-init or rung
    failure lands in an `"error"` field (value stays null) instead of a
    bare rc=1 with nothing to parse — the perf trajectory must never
    have a silent hole."""
    extra = {}
    q6 = None
    error = None
    platform = _ensure_backend()
    extra["backend"] = platform
    try:
        import trino_tpu
        # persistent compile cache: repeat rounds skip XLA recompiles
        trino_tpu.enable_persistent_cache()

        from trino_tpu.connector.tpch import table_row_count
        from trino_tpu.exec import LocalQueryRunner

        sf1 = LocalQueryRunner.tpch("sf1")
        bd6, bd1, bd3 = {}, {}, {}
        q6 = _time_query(sf1, Q6, breakdown=bd6, variant=Q6_VARIANT,
                         prepared=PREPARED["tpch_q6_sf1"])
        q1 = _time_query(sf1, Q1, breakdown=bd1, variant=Q1_VARIANT,
                         prepared=PREPARED["tpch_q1_sf1"])
        extra["tpch_q6_sf1_breakdown"] = bd6
        extra["tpch_q1_sf1_wall_s"] = round(q1, 4)
        extra["tpch_q1_sf1_vs_baseline"] = round(BASE_Q1_SF1_S / q1, 3)
        extra["tpch_q1_sf1_breakdown"] = bd1

        # per-operator totals from one instrumented q6 run (runs outside
        # timing for the per-chain fence cost; since round 13 the
        # instrumented run dispatches the SAME fused executables — see
        # --profile for the full device/compile/host report)
        sf1.session.set("collect_operator_stats", True)
        sf1.execute(Q6)
        extra["tpch_q6_sf1_operators"] = \
            sf1.last_query_stats.get("operators", [])
        sf1.session.properties.pop("collect_operator_stats", None)

        sf10_stats = None
        if platform == "cpu" and \
                os.environ.get("TRINO_TPU_BENCH_SF10") != "force":
            # ~6 timed 60M-row runs on the CPU fallback would eat the
            # whole wall budget; the CPU bench is a diagnostic, not the
            # perf trajectory — skip loudly, overridable
            extra["tpch_q3_sf10_error"] = \
                "skipped: cpu backend (TRINO_TPU_BENCH_SF10=force " \
                "overrides)"
        elif _remaining() > 600:
            sf10 = LocalQueryRunner.tpch("sf10")
            q3 = _time_query(sf10, Q3, breakdown=bd3, variant=Q3_VARIANT,
                             prepared=PREPARED["tpch_q3_sf10"])
            extra["tpch_q3_sf10_wall_s"] = round(q3, 4)
            extra["tpch_q3_sf10_vs_baseline"] = round(
                BASE_Q3_SF10_S / q3, 3)
            extra["tpch_q3_sf10_breakdown"] = bd3

            # BASELINE metric: hash-join probe rows/sec/chip (60M-row
            # lineitem probe into a unique 15M-row orders build)
            probe_rows = table_row_count("lineitem", 10.0)
            jm = _time_query(sf10, JOIN_MICRO, iters=2)
            extra["hash_join_probe_rows_per_s_per_chip"] = \
                round(probe_rows / jm)
            extra["hash_join_vs_baseline"] = round(
                (probe_rows / jm) / BASE_JOIN_ROWS_PER_S, 3)
            sf10_stats = sf10.stats
        else:
            extra["tpch_q3_sf10_error"] = \
                f"skipped: bench wall budget ({BUDGET_S}s) nearly spent"

        sf100_env = os.environ.get("TRINO_TPU_BENCH_SF100", "1")
        if sf100_env == "0" or (platform == "cpu"
                                and sf100_env != "force"):
            # SF100 rungs stream 100GB-scale data; on the CPU fallback
            # they would blow the wall budget without producing a
            # comparable number — record WHY instead of a silent hole
            if sf100_env != "0":
                for tag in SF100_RUNGS:
                    extra[f"{tag}_error"] = \
                        "skipped: cpu backend (SF100 rungs are TPU-scale;" \
                        " TRINO_TPU_BENCH_SF100=force overrides)"
        else:
            for tag, (base, _, _, _) in SF100_RUNGS.items():
                _run_rung_subprocess(extra, tag, base)

        # fault-tolerance counters (round 6): nonzero retries on a clean
        # bench mean the engine degraded (memory-forced spill re-runs) —
        # surfaced so a perf regression caused by silent retries is visible
        extra["retries"] = sf1.stats["retries"] + (
            sf10_stats["retries"] if sf10_stats else 0)
        extra["faults_injected"] = sf1.stats["faults_injected"] + (
            sf10_stats["faults_injected"] if sf10_stats else 0)
    except KeyboardInterrupt as e:
        # still emit the JSON line, but PROPAGATE: an interrupted bench
        # must not exit rc=0 looking green to a gating harness
        error = f"{type(e).__name__}: {str(e)[:300]}"
        interrupted = e
    except BaseException as e:  # noqa: BLE001 — the JSON line must print
        # BaseException, not Exception: a backend-init failure that
        # raises SystemExit (or any exotic non-Exception) used to leave
        # rc=1 with nothing parseable — a silent hole in the perf
        # trajectory. The error rides in the JSON line and the process
        # exits 0; the harness reads `error`, not the return code.
        error = f"{type(e).__name__}: {str(e)[:300]}"
        interrupted = None
    else:
        interrupted = None

    payload = {
        "metric": "tpch_q6_sf1_wall_s",
        "value": round(q6, 4) if q6 is not None else None,
        "unit": "s",
        "extra": extra,
    }
    if q6 is not None:
        payload["vs_baseline"] = round(BASE_Q6_SF1_S / q6, 3)
    if error is not None:
        payload["error"] = error
    print(json.dumps(payload), flush=True)
    if interrupted is not None:
        raise interrupted
    if error is not None:
        sys.exit(0)   # explicit: the JSON line IS the report


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--rung":
        run_rung(sys.argv[2])
    elif len(sys.argv) >= 2 and sys.argv[1] == "--mesh":
        run_mesh(sys.argv[2] if len(sys.argv) >= 3 else None)
    elif len(sys.argv) >= 2 and sys.argv[1] == "--lake":
        run_lake(sys.argv[2] if len(sys.argv) >= 3 else None)
    elif len(sys.argv) >= 2 and sys.argv[1] == "--scrub":
        run_scrub(sys.argv[2] if len(sys.argv) >= 3 else None)
    elif len(sys.argv) >= 2 and sys.argv[1] == "--mv":
        run_mv(sys.argv[2] if len(sys.argv) >= 3 else None)
    elif len(sys.argv) >= 2 and sys.argv[1] == "--qps":
        _qps_args = sys.argv[2:]
        _qps_workers = None
        if "--workers" in _qps_args:
            _i = _qps_args.index("--workers")
            try:
                _qps_workers = [int(x)
                                for x in _qps_args[_i + 1].split(",")]
            except (IndexError, ValueError):
                print("usage: bench.py --qps [OUT.json] "
                      "[--workers N1,N2,...]  (e.g. --workers 0,1,2,4,8)",
                      file=sys.stderr)
                sys.exit(2)
            _qps_args = _qps_args[:_i] + _qps_args[_i + 2:]
        run_qps(_qps_args[0] if _qps_args else None,
                workers=_qps_workers)
    elif len(sys.argv) >= 2 and sys.argv[1] == "--chaos-fleet":
        run_chaos_fleet(sys.argv[2] if len(sys.argv) >= 3 else None)
    elif len(sys.argv) >= 2 and sys.argv[1] == "--preempt":
        run_preempt(sys.argv[2] if len(sys.argv) >= 3 else None)
    elif len(sys.argv) >= 2 and sys.argv[1] == "--memory-ladder":
        run_memory_ladder(sys.argv[2] if len(sys.argv) >= 3 else None)
    elif len(sys.argv) >= 2 and sys.argv[1] == "--profile":
        run_profile(sys.argv[2] if len(sys.argv) >= 3 else None)
    elif len(sys.argv) >= 2 and sys.argv[1] == "--join-micro":
        run_join_micro(sys.argv[2] if len(sys.argv) >= 3 else None)
    else:
        main()
