"""executor: programs an executed query enqueued, the mean of
`stats.kernel_calls` — entries of the activity `kernel_call`, which every
dispatch of `jit_cache.profiled_kernel` and every call of a
`cached_kernel` is. What "fewer, larger dispatches" (ROADMAP B6) sets out
to cut. None for a program without the counter.

Every cell lists this metric, so it is also what has the traced slice
reduced and kept whole as .bench_out/host_timeline.json in the cells
where no metric reads `idle_by_activity` (the dashboard)."""
import host_timeline


def read(ctx):
    host_timeline.table(ctx)
    return host_timeline.counter_mean(ctx, "kernel_calls")
