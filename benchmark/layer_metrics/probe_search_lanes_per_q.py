"""ops kernels: probe lanes an executed query sent through the `search`
lookup (a sort-engine searchsorted a buffer), the mean of
`stats.probe_lookup_lanes_search` — the capacities of the probe buffers of
every join whose lookup `local_planner._prepare_probe` decided `search`
(shapes, no sync), counted beside `probe_lookup_lanes`. Q9's join on
(partkey, suppkey) is there with partsupp's 8 M lanes; a key that gets a
table brings this to 0. None for a program without the counter."""
import host_timeline


def read(ctx):
    return host_timeline.counter_mean(ctx, "probe_lookup_lanes_search")
