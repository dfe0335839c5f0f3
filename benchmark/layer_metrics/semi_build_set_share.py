"""ops kernels: of the lanes of the build pages of the executed queries'
SEMI, ANTI and MARK joins, the share that went into a set table straight
from the keys as they arrived — one scatter, no sort (`ops/join.
build_set_table`, PR 46) — and not through the radix sort of a sorted
build. `exec/local_planner._prepare_probe` books a build page's capacity
(a shape: no sync) to `stats.semi_build_lanes_set` or
`stats.semi_build_lanes_sorted` as it routes it. Q4's `EXISTS` builds
from lineitem's 60 M lanes and takes the set table; Q18's `IN` builds from
the few hundred orders its HAVING kept, which span every order key at
SF10, and is sorted and searched. None for a program without the counters,
or for queries with no such join."""
import host_timeline


def read(ctx):
    as_set = host_timeline.counter_mean(ctx, "semi_build_lanes_set")
    sorted_ = host_timeline.counter_mean(ctx, "semi_build_lanes_sorted")
    if as_set is None or sorted_ is None or as_set + sorted_ == 0:
        return None
    return 100.0 * as_set / (as_set + sorted_)
