"""ops kernels: the least time the chip could take to move the bytes Q9's
join on (partkey, suppkey) must move, whatever implements it — every
partsupp row read (two keys and the supply cost), every line of a matching
part read with the five columns the query carries on (`l_orderkey`,
`l_suppkey`, `l_quantity`, `l_extendedprice`, `l_discount`) beside its two
keys, and every joined row written (those five and the supply cost) — per
executed q9 of the traced slice, all the cell's chips at their peak HBM
rate, over the seconds the trace gives to that join's programs
(`composite_join_device_ms_per_q`). Row counts are the reference's, not the
program's: partsupp's from the configuration's `rows`, the lines of
matching parts lineitem's rows times the share of part names that match the
request's COLOR (`queries/q9.py`'s `matches`; `p_name`'s two words are
uniform, so the share is the expectation, within 0.2 % of the count at
SF10); each of them joins one partsupp row. A q9 partly in the slice counts
by the share of its time that is, as in query_hbm_roofline."""
import trace_programs
from reference import load_by_path

composite_seconds = load_by_path(
    "layer_metrics", "composite_join_device_ms_per_q").composite_seconds

SHAPE = "q9"
PROBE_ROW = 8 + 8 + 8           # ps_partkey, ps_suppkey, ps_supplycost
BUILD_ROW = 2 * 8 + 5 * 8       # the two keys and five carried columns
OUTPUT_ROW = 5 * 8 + 8          # the five and ps_supplycost


def join_bytes(rows: dict, matching_share: float) -> float:
    lines = rows["lineitem"] * matching_share
    return rows["partsupp"] * PROBE_ROW + lines * (BUILD_ROW + OUTPUT_ROW)


def read(ctx):
    table = trace_programs.table(ctx)
    shape = ctx.get("shapes", {}).get(SHAPE)
    if not table or not ctx.get("peaks") or shape is None:
        return None
    seconds = composite_seconds(table)
    lo, hi = ctx["slice"]
    rows = shape.table_rows(ctx["config"]["rows"])
    needed = 0.0
    for r in trace_programs.executed(ctx):
        overlap = min(r["t_done"], hi) - max(r["t_send"], lo)
        if r["shape"] == SHAPE and overlap > 0:
            needed += join_bytes(rows, shape.matches(
                r["params"]["color"]).mean()) \
                * overlap / (r["t_done"] - r["t_send"])
    if seconds <= 0 or needed <= 0:
        return None
    share = 100.0 * needed \
        / (len(ctx["chips"]) * ctx["peaks"]["hbm_bytes_per_s"]) / seconds
    if share > 100.0:
        raise ValueError(
            f"composite_join_hbm_roofline {share:.1f} % is above 100: "
            "bytes are counted too high or the join's programs' time "
            "leaves out part of the work")
    return share
