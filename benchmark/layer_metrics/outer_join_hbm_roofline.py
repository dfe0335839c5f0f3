"""ops kernels: the least time the chip could take to move the bytes Q13's
LEFT OUTER JOIN must move, whatever implements it — every customer key
read, every kept order read (`o_custkey`, `o_orderkey`), every joined row
written (`c_custkey`, `o_orderkey` and its validity) — per executed q13 of
the traced slice, all the cell's chips at their peak HBM rate, over the
seconds the trace gives to the outer join's programs
(`outer_join_device_ms_per_q`). Row counts are the reference's, not the
program's: customers and orders from the configuration's `rows`; the kept
orders are the orders times the share of `o_comment`'s phrases the
request's WORD1 and WORD2 keep (`queries/q13.py`'s `kept_orders`: every
phrase is as likely as another); the joined rows are the kept orders and
one null-extended row for each customer without one — the third of the
customers the generator gives no order (28 more at SF10 by chance, and a
customer all of whose orders are excluded: under one in a million). A q13
partly in the slice counts by the share of its time that is, as in
query_hbm_roofline."""
import trace_programs
from reference import load_by_path

outer_seconds = load_by_path(
    "layer_metrics", "outer_join_device_ms_per_q").outer_seconds

SHAPE = "q13"
PROBE_ROW = 8               # c_custkey
BUILD_ROW = 8 + 8           # o_custkey, o_orderkey
OUTPUT_ROW = 8 + 8 + 1      # c_custkey, o_orderkey and its validity


def join_bytes(rows: dict, kept_orders: float) -> float:
    joined = kept_orders + rows["customer"] // 3
    return rows["customer"] * PROBE_ROW + kept_orders * BUILD_ROW \
        + joined * OUTPUT_ROW


def read(ctx):
    table = trace_programs.table(ctx)
    shape = ctx.get("shapes", {}).get(SHAPE)
    if not table or not ctx.get("peaks") or shape is None:
        return None
    seconds = outer_seconds(table)
    lo, hi = ctx["slice"]
    rows = shape.table_rows(ctx["config"]["rows"])
    needed = 0.0
    for r in trace_programs.executed(ctx):
        overlap = min(r["t_done"], hi) - max(r["t_send"], lo)
        if r["shape"] == SHAPE and overlap > 0:
            needed += join_bytes(rows, shape.kept_orders(
                rows["orders"], r["params"])) \
                * overlap / (r["t_done"] - r["t_send"])
    if seconds <= 0 or needed <= 0:
        return None
    share = 100.0 * needed \
        / (len(ctx["chips"]) * ctx["peaks"]["hbm_bytes_per_s"]) / seconds
    if share > 100.0:
        raise ValueError(
            f"outer_join_hbm_roofline {share:.1f} % is above 100: "
            "bytes are counted too high or the join's programs' time "
            "leaves out part of the work")
    return share
