"""ops kernels: the least time the chip could take to move the bytes Q18's
inner GROUP BY l_orderkey must move — key and quantity of every lineitem
row read, key and sum of every group written (`groupby_bytes`, from the
configuration's `rows`) — per executed q18 of the traced slice, all the
cell's chips at their peak HBM rate, over the seconds the trace gives to
the `aggregate` family in the slice (`trace_programs.py`). It reads the
same work whatever implements it: a sort, a hash table or a direct table.
A q18 that lies partly in the slice counts by the share of its time that
does, as in query_hbm_roofline."""
import trace_programs

SHAPE = "q18"


def groupby_bytes(rows: dict) -> int:
    return (rows["lineitem"] + rows["orders"]) * (8 + 8)


def read(ctx):
    table = trace_programs.table(ctx)
    if not table or not ctx.get("peaks"):
        return None
    seconds = table["by_family"].get("aggregate", 0.0)
    queries = trace_programs.executed_in_slice({**ctx, "requests": [
        r for r in ctx["requests"] if r["shape"] == SHAPE]})
    if seconds <= 0 or queries <= 0:
        return None
    share = 100.0 * queries * groupby_bytes(ctx["config"]["rows"]) \
        / (len(ctx["chips"]) * ctx["peaks"]["hbm_bytes_per_s"]) / seconds
    if share > 100.0:
        raise ValueError(
            f"groupby_hbm_roofline {share:.1f} % is above 100: bytes are "
            "counted too high or the aggregate family's time leaves out "
            "part of the work")
    return share
