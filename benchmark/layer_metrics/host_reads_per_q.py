"""executor: times an executed query's thread stopped to read a device
value — a row count, a key range, an overflow flag — the mean of
`stats.host_reads`: entries of the activity `host_read`, which every
`jit_cache.host_read` is. Each is a drain-decide-refill point of the
device queue. None for a program without the counter."""
import host_timeline


def read(ctx):
    return host_timeline.counter_mean(ctx, "host_reads")
