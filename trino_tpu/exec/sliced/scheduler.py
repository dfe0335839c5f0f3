"""SliceScheduler: bounded-work slices over page-producing pipelines.

The engine's execution frontier is page production (every streaming
operator is a lazy transform fused onto the leaf's pages; blocking
operators consume the leaf eagerly), so the slice loop lives there: the
scheduler wraps a page iterator, accumulates produced rows, and when
the row budget fills it runs the SLICE BOUNDARY protocol —

  - the cooperative checkpoint (deadline/cancel check + low-memory-kill
    poll) the engine acts through: DELETE, the killer, and serve-tier
    backpressure all take effect here, between device dispatches, with
    no cooperation from the kernel body;
  - the chaos site `slice` (exec/faults.py), so fault injection can
    kill a query mid-operator between two slices;
  - budget retune: a wall-clock EWMA sizes the NEXT slice so one slice
    costs ~`slice_target_ms` regardless of row width or backend speed —
    the row budget is the mechanism, wall time is the contract
    (cancellation latency is bounded by ONE slice's wall).

The session's static budget also bounds SCAN PAGE CAPACITY (the local
planner consults `capacity_cap`): without it a statistics-grown scan page is one
multi-million-row kernel the engine cannot preempt, which is exactly
the wedged-kernel problem this subsystem exists to remove. In-kernel
preemption of a single mega-slice (a checkpointing kernel body) stays
open — ROADMAP item 5.
"""

from __future__ import annotations

import time
from typing import Iterator, Optional

DEFAULT_TARGET_ROWS = 1 << 20
MIN_TARGET_ROWS = 1 << 12
MAX_TARGET_ROWS = 1 << 23
# EWMA smoothing for the measured rows/second the retune steers by
_ALPHA = 0.3


class SliceScheduler:
    """Per-query slice driver, shared by every executor (local pipeline,
    distributed shard tasks) the query runs: counters aggregate across
    them and the budget tunes globally. Single-threaded by construction
    (one query executes on one thread; shards dispatch sequentially)."""

    def __init__(self, target_rows: int = DEFAULT_TARGET_ROWS,
                 target_ms: float = 0.0,
                 min_rows: int = MIN_TARGET_ROWS,
                 max_rows: int = MAX_TARGET_ROWS):
        self.target_rows = max(int(target_rows), 1)
        # the session's own budget, never retuned: what bounds a scan
        # page (`capacity_cap`)
        self.page_rows = self.target_rows
        self.target_ms = float(target_ms)
        self.min_rows = max(1, int(min_rows))
        self.max_rows = max(self.min_rows, int(max_rows))
        # counters (rolled into the query's stats snapshot by the runner)
        self.slices_executed = 0
        self.slice_rows = 0
        self.max_slice_wall_s = 0.0
        # rows/second EWMA behind the retune (None until first measure)
        self._rows_per_s: Optional[float] = None

    @classmethod
    def from_session(cls, session) -> Optional["SliceScheduler"]:
        """The query's scheduler, or None when `sliced_execution` is
        off (the debugging pin back to unbounded operator runs)."""
        if not bool(session.get("sliced_execution")):
            return None
        return cls(int(session.get("slice_target_rows")),
                   float(session.get("slice_target_ms")))

    # ------------------------------------------------------------ budget

    def capacity_cap(self, floor: int) -> int:
        """Pow2 page-capacity bound for leaf scans: one scan page must
        never exceed a slice (a bigger page is one un-preemptible kernel
        launch). `floor` is the session page capacity — slicing never
        shrinks pages below the engine's normal streaming grain.

        Read from the session's STATIC budget (`slice_target_rows`), not
        from the budget the clock retunes: a page's capacity is a
        compiled program's shape, and a plan lowers its scans as it
        executes, so a cap that followed the EWMA gave a query's later
        scans the shapes its earlier slices' wall time chose — TPC-H Q9 at
        SF10 (six scans) alternated between two sets of shapes from one
        request to the next, 559 page pulls then 291, each warming the
        connector's column cache for the other's timing, and every shape
        the warm-up had not met compiled inside the window (PR 42; ROADMAP
        D14). The retune still sizes the row budget between checkpoints."""
        cap = 1 << (max(self.page_rows, 1) - 1).bit_length()
        return max(cap, floor)

    def observe(self, rows: int, wall_s: float) -> None:
        """Feed one slice's measured (rows, wall) into the EWMA and
        retune the row budget toward `slice_target_ms`. No-op when wall
        tuning is disabled (target_ms <= 0): the static row budget
        binds."""
        self.max_slice_wall_s = max(self.max_slice_wall_s, wall_s)
        if self.target_ms <= 0 or rows <= 0 or wall_s <= 0:
            return
        rate = rows / wall_s
        if self._rows_per_s is None:
            self._rows_per_s = rate
        else:
            self._rows_per_s += _ALPHA * (rate - self._rows_per_s)
        tuned = int(self._rows_per_s * self.target_ms / 1000.0)
        self.target_rows = min(max(tuned, self.min_rows), self.max_rows)

    # -------------------------------------------------------- the loop

    def run(self, pages: Iterator, checkpoint=None,
            fault_site=None) -> Iterator:
        """Drive a page iterator as bounded-work slices: yield pages
        through, and between slices run the boundary protocol
        (`checkpoint` = the executor's cooperative cancel/kill check,
        `fault_site` = the executor's chaos hook). The FINAL partial
        slice counts too — a query that produced anything executed at
        least one slice."""
        budget = self.target_rows
        used = 0
        t0 = time.perf_counter()
        for page in pages:
            yield page
            used += _row_estimate(page)
            if used >= budget:
                now = time.perf_counter()
                self.slices_executed += 1
                self.slice_rows += used
                self.observe(used, now - t0)
                if fault_site is not None:
                    fault_site("slice", f"rows {used}")
                if checkpoint is not None:
                    checkpoint()
                budget = self.target_rows   # retuned
                used = 0
                t0 = time.perf_counter()
        if used:
            self.slices_executed += 1
            self.slice_rows += used
            self.observe(used, time.perf_counter() - t0)


def _row_estimate(page) -> int:
    """Host-known row count of a page WITHOUT a device sync: leaf scans
    carry python-int counts; a traced/device count falls back to the
    page capacity (an over-estimate only tightens the slice)."""
    n = getattr(page, "num_rows", None)
    if isinstance(n, int):
        return n
    try:
        import numpy as np
        if isinstance(n, np.integer):
            return int(n)
    except Exception:   # pragma: no cover - numpy always present
        pass
    return int(getattr(page, "capacity", 0) or 0)
