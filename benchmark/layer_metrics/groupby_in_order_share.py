"""ops kernels: of the lanes the executed queries' sorted GROUP BYs ran
over, the share the device found already in key order and ran with no
sort, no permutation and no gather (`ops/aggregate._in_key_order`, PR 45).
The kernel books a dispatch's capacity to `stats.group_by_lanes_in_order`
or `stats.group_by_lanes_sorted` beside the page it returns, read with the
query's row counts at its end (no new sync). Q18's GROUP BY on
`l_orderkey` over lineitem and Q13's on `c_custkey` over the join's output
arrive in order; Q9's on (nation, year) and Q4 do not. None for a program
without the counters, or for queries with no sorted GROUP BY."""
import host_timeline


def read(ctx):
    in_order = host_timeline.counter_mean(ctx, "group_by_lanes_in_order")
    sorted_ = host_timeline.counter_mean(ctx, "group_by_lanes_sorted")
    if in_order is None or sorted_ is None or in_order + sorted_ == 0:
        return None
    return 100.0 * in_order / (in_order + sorted_)
