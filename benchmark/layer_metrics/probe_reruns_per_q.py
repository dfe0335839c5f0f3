"""executor: probe pages an executed query ran a second time at a larger
output capacity, the mean of `stats.probe_overflow_reruns` — counted in
`local_planner._run_with_overflow` where a page's true total exceeds the
capacity its join first ran at (`max(page_capacity, page.capacity)`), on
the host from the totals it reads anyway (no new sync). Every such page
runs the whole join kernel twice: an expanding join (Q13's customers to
their ten orders each) reads one a probe page until the first capacity
follows the build's run lengths. None for a program without the
counter."""
import host_timeline


def read(ctx):
    return host_timeline.counter_mean(ctx, "probe_overflow_reruns")
