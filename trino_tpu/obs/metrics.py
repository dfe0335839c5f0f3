"""Process-wide metrics registry with Prometheus text rendering.

Reference parity: the reference exports engine counters through JMX
MBeans (io.airlift.stats CounterStat/DistributionStat on QueryManager,
MemoryPool, resource groups) and publishes them as OpenMetrics via the
jmx-prometheus agent every production deployment runs. Here the registry
is native: counters/histograms are fed by query lifecycle events
(obs/listeners.py), and gauges SAMPLE live engine state at scrape time —
the query tracker, the node memory pool, every live resource-group tree,
and the jit kernel cache — so `GET /v1/metrics` and
`system.runtime.metrics` always reflect the current process without any
background collection thread.

Naming follows Prometheus conventions: `trino_tpu_` prefix, `_total`
suffix on monotonic counters, base units (bytes, seconds).
"""

from __future__ import annotations

import math
import threading
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

LabelSet = Tuple[Tuple[str, str], ...]

# query wall-clock histogram buckets (seconds): spans compile-dominated
# millisecond queries to SF100 multi-minute rungs. The DEFAULT is
# session-independent and overridable process-wide via
# $TRINO_TPU_METRICS_WALL_BUCKETS (comma-separated seconds) or per
# deployment via TrinoServer(metrics_wall_buckets=...) -> set_wall_buckets
DEFAULT_WALL_BUCKETS = (0.005, 0.025, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0,
                        600.0)


def _env_wall_buckets() -> Tuple[float, ...]:
    import os
    raw = os.environ.get("TRINO_TPU_METRICS_WALL_BUCKETS", "")
    try:
        out = tuple(sorted(float(x) for x in raw.split(",") if x.strip()))
    except ValueError:
        return DEFAULT_WALL_BUCKETS
    return out or DEFAULT_WALL_BUCKETS


WALL_BUCKETS = _env_wall_buckets()

# preemption-latency buckets (seconds): cancel-request -> unwind is
# slice-bounded, so the interesting range is milliseconds to a few
# seconds, far below query walls
PREEMPT_BUCKETS = (0.001, 0.005, 0.025, 0.1, 0.25, 0.5, 1.0, 5.0, 30.0)


def _labels(kw: Dict[str, Any]) -> LabelSet:
    return tuple(sorted((str(k), str(v)) for k, v in kw.items()))


def _render_labels(labels: LabelSet) -> str:
    if not labels:
        return ""
    body = ",".join(
        '%s="%s"' % (k, v.replace("\\", "\\\\").replace('"', '\\"')
                     .replace("\n", "\\n"))
        for k, v in labels)
    return "{" + body + "}"


class Counter:
    """Monotonic counter family (one value per label set). `labeled`
    families never fabricate an unlabeled zero sample: a placeholder
    series that vanishes after the first real labeled increment reads as
    a counter reset to anything monitoring it."""

    def __init__(self, registry: "MetricsRegistry", name: str, help: str,
                 labeled: bool = False):
        self.name = name
        self.help = help
        self.labeled = labeled
        self._registry = registry
        self._values: Dict[LabelSet, float] = {}

    def inc(self, amount: float = 1.0, **labels) -> None:
        if amount == 0:
            return
        key = _labels(labels)
        with self._registry._lock:
            self._values[key] = self._values.get(key, 0.0) + float(amount)

    def samples(self) -> Iterable[Tuple[str, LabelSet, float]]:
        with self._registry._lock:
            items = list(self._values.items())
        if not items:
            if self.labeled:
                return              # family header only, no samples yet
            items = [((), 0.0)]     # label-less family exists from birth
        for key, value in items:
            yield self.name, key, value


class Histogram:
    """Cumulative-bucket histogram family (Prometheus semantics).
    `labeled` families render no samples until the first observation —
    same phantom-series discipline as labeled counters (an unlabeled
    zero series that vanishes after the first real labeled observation
    reads as a reset)."""

    def __init__(self, registry: "MetricsRegistry", name: str, help: str,
                 buckets: Tuple[float, ...] = WALL_BUCKETS,
                 labeled: bool = False):
        self.name = name
        self.help = help
        self.labeled = labeled
        self.buckets = tuple(sorted(buckets))
        self._registry = registry
        self._counts: Dict[LabelSet, List[int]] = {}
        self._sums: Dict[LabelSet, float] = {}
        self._totals: Dict[LabelSet, int] = {}

    def observe(self, value: float, **labels) -> None:
        key = _labels(labels)
        with self._registry._lock:
            counts = self._counts.setdefault(key, [0] * len(self.buckets))
            for i, b in enumerate(self.buckets):
                if value <= b:
                    counts[i] += 1
            self._sums[key] = self._sums.get(key, 0.0) + float(value)
            self._totals[key] = self._totals.get(key, 0) + 1

    def set_buckets(self, buckets: Tuple[float, ...]) -> None:
        """Re-bucket the family (deployment configuration — TrinoServer
        metrics_wall_buckets). Bucket counts are per-observation
        cumulative, so prior observations cannot be re-binned: the
        family RESETS (counts, sums, totals) — same visible effect as a
        process restart with the new buckets, which is when bucket
        boundaries legitimately change. A scrape-side monitor sees a
        counter reset, the semantics Prometheus defines for restarts."""
        with self._registry._lock:
            self.buckets = tuple(sorted(buckets))
            self._counts.clear()
            self._sums.clear()
            self._totals.clear()

    def samples(self) -> Iterable[Tuple[str, LabelSet, float]]:
        with self._registry._lock:
            keys = list(self._counts) or ([] if self.labeled else [()])
            counts = {k: list(v) for k, v in self._counts.items()}
            sums, totals = dict(self._sums), dict(self._totals)
        for key in keys:
            cum = counts.get(key, [0] * len(self.buckets))
            for b, c in zip(self.buckets, cum):
                yield (self.name + "_bucket",
                       key + (("le", _fmt_float(b)),), float(c))
            yield (self.name + "_bucket", key + (("le", "+Inf"),),
                   float(totals.get(key, 0)))
            yield self.name + "_sum", key, sums.get(key, 0.0)
            yield self.name + "_count", key, float(totals.get(key, 0))


def _fmt_float(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    out = repr(float(v))
    return out[:-2] if out.endswith(".0") else out


class MetricsRegistry:
    """Instrument + gauge-callback registry; render() is the scrape."""

    def __init__(self):
        self._lock = threading.RLock()
        self._counters: Dict[str, Counter] = {}
        self._histograms: Dict[str, Histogram] = {}
        # each callback yields (name, help, value, labels_dict) gauge
        # samples from live engine state at scrape time
        self._gauge_callbacks: List[Callable[[], Iterable[tuple]]] = []

    def counter(self, name: str, help: str,
                labeled: bool = False) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(self, name, help,
                                                   labeled)
            return c

    def histogram(self, name: str, help: str,
                  buckets: Tuple[float, ...] = WALL_BUCKETS,
                  labeled: bool = False) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram(self, name, help,
                                                       buckets, labeled)
            return h

    def register_gauges(self, callback: Callable[[], Iterable[tuple]]
                        ) -> None:
        with self._lock:
            if callback not in self._gauge_callbacks:
                self._gauge_callbacks.append(callback)

    # ---------------------------------------------------------- scrape

    def _gauge_samples(self) -> List[Tuple[str, str, LabelSet, float]]:
        out = []
        with self._lock:
            callbacks = list(self._gauge_callbacks)
        for cb in callbacks:
            try:
                for name, help, value, labels in cb():
                    out.append((name, help, _labels(labels), float(value)))
            except Exception:   # a broken sampler must not fail the scrape
                continue
        return out

    def render(self) -> str:
        """The Prometheus text exposition (format 0.0.4): families
        grouped under one HELP/TYPE header each."""
        lines: List[str] = []
        with self._lock:
            counters = list(self._counters.values())
            histograms = list(self._histograms.values())
        for c in sorted(counters, key=lambda c: c.name):
            lines.append(f"# HELP {c.name} {c.help}")
            lines.append(f"# TYPE {c.name} counter")
            for name, labels, value in c.samples():
                lines.append(f"{name}{_render_labels(labels)} "
                             f"{_fmt_value(value)}")
        for h in sorted(histograms, key=lambda h: h.name):
            lines.append(f"# HELP {h.name} {h.help}")
            lines.append(f"# TYPE {h.name} histogram")
            for name, labels, value in h.samples():
                lines.append(f"{name}{_render_labels(labels)} "
                             f"{_fmt_value(value)}")
        gauges = self._gauge_samples()
        seen_header = set()
        for name, help, labels, value in sorted(gauges):
            if name not in seen_header:
                seen_header.add(name)
                lines.append(f"# HELP {name} {help}")
                lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name}{_render_labels(labels)} "
                         f"{_fmt_value(value)}")
        return "\n".join(lines) + "\n"

    def samples(self) -> List[Tuple[str, str, str, float]]:
        """(name, kind, labels, value) rows for system.runtime.metrics —
        the same data render() exposes, shaped for a table scan."""
        rows: List[Tuple[str, str, str, float]] = []
        with self._lock:
            counters = list(self._counters.values())
            histograms = list(self._histograms.values())
        for c in counters:
            for name, labels, value in c.samples():
                rows.append((name, "counter", _render_labels(labels)[1:-1],
                             value))
        for h in histograms:
            for name, labels, value in h.samples():
                rows.append((name, "histogram", _render_labels(labels)[1:-1],
                             value))
        for name, _help, labels, value in self._gauge_samples():
            rows.append((name, "gauge", _render_labels(labels)[1:-1], value))
        return sorted(rows)


def _fmt_value(v: float) -> str:
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


# the process-wide registry (singleton scope, like TRACKER / NODE_POOL)
REGISTRY = MetricsRegistry()

# counter/histogram families fed by query lifecycle events
# (obs/listeners.py fires these on every tracker transition)
QUERIES_TOTAL = REGISTRY.counter(
    "trino_tpu_queries_total",
    "Queries reaching a terminal state, by state.", labeled=True)
QUERY_ROWS_TOTAL = REGISTRY.counter(
    "trino_tpu_query_rows_total", "Result rows returned by queries.")
QUERY_BYTES_TOTAL = REGISTRY.counter(
    "trino_tpu_query_bytes_total", "Output bytes produced by queries.")
QUERY_RETRIES_TOTAL = REGISTRY.counter(
    "trino_tpu_query_retries_total",
    "Task/query retry attempts across all queries.")
FAULTS_INJECTED_TOTAL = REGISTRY.counter(
    "trino_tpu_faults_injected_total",
    "Chaos faults injected across all queries.")
SPILLED_BYTES_TOTAL = REGISTRY.counter(
    "trino_tpu_query_spilled_bytes_total",
    "Bytes spilled to host partitions across all queries.")
QUERY_WALL_SECONDS = REGISTRY.histogram(
    "trino_tpu_query_wall_seconds",
    "Query wall-clock duration from start to terminal state.")
EXCHANGE_BYTES_TOTAL = REGISTRY.counter(
    "trino_tpu_exchange_bytes_total",
    "Bytes moved through inter-fragment exchanges (on-device "
    "collectives; live-row estimate).")
EXCHANGE_ROWS_TOTAL = REGISTRY.counter(
    "trino_tpu_exchange_rows_total",
    "Rows moved through inter-fragment exchanges.")
EXCHANGES_TOTAL = REGISTRY.counter(
    "trino_tpu_exchanges_total",
    "Inter-fragment exchanges by data-plane mode: 'fused' = collective "
    "inlined in a co-scheduled mesh program (pages never leave the "
    "producing XLA program); 'staged' = standalone collective over "
    "host-staged per-shard fragment outputs.", labeled=True)
SLICES_TOTAL = REGISTRY.counter(
    "trino_tpu_slices_total",
    "Bounded-work execution slices completed across all queries "
    "(preemptible sliced execution, exec/sliced/).")
CHECKPOINTS_TOTAL = REGISTRY.counter(
    "trino_tpu_checkpoints_total",
    "Operator checkpoints by operation: 'saved' = durable state written "
    "at a slice/shard boundary; 'restored' = a retry resumed from one "
    "instead of re-executing.", labeled=True)
CHECKPOINT_BYTES_TOTAL = REGISTRY.counter(
    "trino_tpu_checkpoint_bytes_total",
    "Bytes of operator state checkpointed across all queries.")
PREEMPTIONS_TOTAL = REGISTRY.counter(
    "trino_tpu_preemptions_total",
    "Queries preempted (canceled/killed between slices) across the "
    "process lifetime.")
ADAPTIVE_EVENTS_TOTAL = REGISTRY.counter(
    "trino_tpu_adaptive_events_total",
    "Adaptive operator strategy events by kind: partial-aggregation "
    "mode transitions (agg_mode_downgrades/agg_mode_upgrades), "
    "recursive spill repartition rounds (agg_recursions/"
    "join_recursions), heavy-hitter key splits (heavy_key_splits), and "
    "bounded chunked fallbacks at max recursion depth "
    "(spill_fallbacks).", labeled=True)
PREEMPT_LATENCY_SECONDS = REGISTRY.histogram(
    "trino_tpu_preempt_latency_seconds",
    "Cancel-request to unwind wall per preempted query — bounded by "
    "one slice's wall under sliced execution.",
    buckets=PREEMPT_BUCKETS)
GROUP_WALL_SECONDS = REGISTRY.histogram(
    "trino_tpu_group_wall_seconds",
    "Query wall-clock duration by resource group and terminal outcome "
    "(FINISHED/FAILED/CANCELED) — the per-group latency/SLO surface the "
    "serving tier alerts on.", labeled=True)
LISTENER_ERRORS_TOTAL = REGISTRY.counter(
    "trino_tpu_listener_errors_total",
    "Event-listener callbacks that raised, by listener type. Failures "
    "are swallowed (a broken plugin must not fail queries) and logged "
    "once per listener; this counter is the ongoing signal.",
    labeled=True)
COMPILE_SECONDS_TOTAL = REGISTRY.counter(
    "trino_tpu_query_compile_seconds_total",
    "Summed XLA compile wall attributed to queries (measured at the "
    "jit cache's AOT compile sites) — the compile half of "
    "compile-vs-execute accounting.")
DEVICE_SECONDS_TOTAL = REGISTRY.counter(
    "trino_tpu_query_device_seconds_total",
    "Summed measured device wall attributed to queries (fused-chain "
    "dispatches fenced at chain granularity under operator-level "
    "collection).")
MV_REFRESH_TOTAL = REGISTRY.counter(
    "trino_tpu_mv_refresh_total",
    "Materialized-view refreshes by mode: 'delta' = incremental merge "
    "over the manifest-log diff, 'full' = complete recompute, 'noop' = "
    "base versions unchanged since the last refresh.", labeled=True)
MV_REFRESH_SECONDS_TOTAL = REGISTRY.counter(
    "trino_tpu_mv_refresh_seconds_total",
    "Summed wall-clock spent executing materialized-view refreshes.")
MV_REWRITE_HITS_TOTAL = REGISTRY.counter(
    "trino_tpu_mv_rewrite_hits_total",
    "Queries rewritten onto a fresh materialized view's storage table.")
MV_REWRITE_STALE_TOTAL = REGISTRY.counter(
    "trino_tpu_mv_rewrite_stale_total",
    "Rewrite/serve attempts refused because the view exceeded the "
    "session's mv_max_staleness_s budget.")
MV_CACHE_REPUBLISH_TOTAL = REGISTRY.counter(
    "trino_tpu_mv_cache_republish_total",
    "Result-cache entries UPDATED in place by a refresh (the "
    "update-on-write flip: re-executed rewritten statements republished "
    "under their original keys).")


def set_wall_buckets(buckets) -> None:
    """Deployment-time bucket configuration for the wall histograms
    (TrinoServer(metrics_wall_buckets=...)); resets the families — see
    Histogram.set_buckets. Applies to BOTH wall families: the per-group
    SLO histogram alerts on the same latency envelope the deployment
    tuned the query-wall buckets for."""
    bounds = tuple(float(b) for b in buckets)
    QUERY_WALL_SECONDS.set_buckets(bounds)
    GROUP_WALL_SECONDS.set_buckets(bounds)


def _engine_gauges():
    """Live engine state sampled at scrape time: tracker states, node
    memory pool, resource groups, jit kernel cache."""
    from trino_tpu.exec.query_tracker import TRACKER
    states: Dict[str, int] = {}
    for q in TRACKER.list():
        states[q.state] = states.get(q.state, 0) + 1
    for state, n in sorted(states.items()):
        yield ("trino_tpu_queries", "Tracked queries by lifecycle state.",
               n, {"state": state})

    from trino_tpu.exec.memory import NODE_POOL
    pool = "Node memory pool "
    yield ("trino_tpu_pool_limit_bytes", pool + "reservable budget.",
           NODE_POOL.limit or 0, {})
    yield ("trino_tpu_pool_reserved_bytes", pool + "current reservation.",
           NODE_POOL.reserved, {})
    yield ("trino_tpu_pool_peak_bytes", pool + "peak reservation.",
           NODE_POOL.peak, {})
    yield ("trino_tpu_pool_kills", pool + "low-memory-killer victims.",
           NODE_POOL.kills, {})
    yield ("trino_tpu_pool_leaks", pool + "reservation leaks at query end.",
           NODE_POOL.leaks, {})
    yield ("trino_tpu_pool_leaked_bytes", pool + "bytes leaked total.",
           NODE_POOL.leaked_bytes, {})
    for d in sorted(set(NODE_POOL.device_reserved)
                    | set(NODE_POOL.device_peak)):
        labels = {"device": d}
        yield ("trino_tpu_pool_device_reserved_bytes",
               pool + "current reservation attributed per mesh device.",
               NODE_POOL.device_reserved.get(d, 0), labels)
        yield ("trino_tpu_pool_device_peak_bytes",
               pool + "peak reservation attributed per mesh device.",
               NODE_POOL.device_peak.get(d, 0), labels)

    from trino_tpu.exec.spill import SPILL_LEDGER
    spill = "Spill partition stores: "
    yield ("trino_tpu_spill_bytes",
           spill + "host RAM currently held by spilled partitions.",
           SPILL_LEDGER.reserved, {})
    yield ("trino_tpu_spill_peak_bytes",
           spill + "peak host RAM held since process start.",
           SPILL_LEDGER.peak, {})
    yield ("trino_tpu_spill_limit_denials",
           spill + "reservations denied by a query's spill_max_bytes "
           "budget (EXCEEDED_SPILL_LIMIT failures).",
           SPILL_LEDGER.denials, {})

    from trino_tpu.exec.resource_groups import list_all_groups
    for g in list_all_groups():
        labels = {"group": g.name}
        yield ("trino_tpu_resource_group_queued",
               "Queued queries per resource group.", g.queued, labels)
        yield ("trino_tpu_resource_group_running",
               "Running queries per resource group.", len(g.running),
               labels)
        yield ("trino_tpu_resource_group_served_from_cache",
               "Completed queries answered from the result cache per "
               "resource group (zero-dispatch fast path; counted so "
               "group QPS quotas see cached traffic).",
               g.served_from_cache, labels)
        if g.cache_hit_rejections or g.result_cache_qps is not None:
            yield ("trino_tpu_resource_group_cache_hit_rejections",
                   "Fast-path hits rejected by the group's "
                   "result_cache_qps token bucket (QUERY_QUEUE_FULL "
                   "on the wire).",
                   g.cache_hit_rejections, labels)

    from trino_tpu.exec import jit_cache
    js = jit_cache.stats()
    yield ("trino_tpu_jit_cache_kernels",
           "Compiled kernels resident in the jit cache.", js["size"], {})
    yield ("trino_tpu_jit_cache_hits",
           "Jit cache hits since process start.", js["hits"], {})
    yield ("trino_tpu_jit_cache_misses",
           "Jit cache misses (kernel builds) since process start.",
           js["misses"], {})
    yield ("trino_tpu_jit_cache_param_hits",
           "Hits on a canonical (literal-hoisted) key whose parameter "
           "values changed since that key's previous call — kernel "
           "sharing per-literal keying could not have expressed.",
           js["param_hits"], {})
    yield ("trino_tpu_jit_cache_evictions_total",
           "Kernels evicted from the in-process LRU since process start "
           "(evicted shapes reload from the persistent XLA cache).",
           js["evictions"], {})
    yield ("trino_tpu_jit_compiles_total",
           "XLA compiles performed through the profiled dispatch path "
           "(one per new input signature of a chain/program kernel) — "
           "each one a timed, query-attributed event.",
           js["compiles"], {})
    yield ("trino_tpu_jit_compile_seconds_total",
           "Summed wall of profiled-path XLA compiles since process "
           "start.", js["compile_s"], {})
    yield ("trino_tpu_jit_compiled_hlo_ops_total",
           "Summed HLO instruction count of profiled-path compiles.",
           js["hlo_ops"], {})
    yield ("trino_tpu_jit_aot_fallbacks_total",
           "Profiled dispatches that fell back to the plain jitted "
           "callable (signature mismatch at call time) — a systematic "
           "nonzero rate means the AOT accounting path is misfiring.",
           js["aot_fallbacks"], {})

    from trino_tpu.obs.history import HISTORY
    hs = HISTORY.stats()
    hist = "Query-history ring (obs/history.py): "
    yield ("trino_tpu_history_entries",
           hist + "completed queries currently retained.",
           hs["entries"], {})
    yield ("trino_tpu_history_max_entries",
           hist + "retention bound (history_max_entries).",
           hs["max_entries"], {})
    yield ("trino_tpu_history_recorded_total",
           hist + "terminal queries recorded since process start.",
           hs["recorded"], {})
    yield ("trino_tpu_history_evicted_total",
           hist + "records dropped by the FIFO bound.",
           hs["evicted"], {})

    from trino_tpu.exec import plan_cache
    ps = plan_cache.stats()
    yield ("trino_tpu_plan_cache_entries",
           "Optimized plans resident across live plan caches.",
           ps["entries"], {})
    yield ("trino_tpu_plan_cache_hits",
           "Plan cache hits since process start — statements that "
           "skipped parse/analyze/plan/optimize.", ps["hits"], {})
    yield ("trino_tpu_plan_cache_misses",
           "Plan cache misses (full plans built) since process start.",
           ps["misses"], {})
    yield ("trino_tpu_plan_cache_evictions_total",
           "Plans evicted by the per-runner LRU since process start.",
           ps["evictions"], {})
    yield ("trino_tpu_plan_cache_invalidations_total",
           "Plans dropped by DDL/INSERT table invalidation since "
           "process start.", ps["invalidations"], {})

    from trino_tpu.serve.caches import (result_cache_stats,
                                        scan_cache_stats)
    rs = result_cache_stats()
    yield ("trino_tpu_result_cache_entries",
           "Materialized results resident across live result caches.",
           rs["entries"], {})
    yield ("trino_tpu_result_cache_hits",
           "Result cache hits since process start — statements answered "
           "with zero planning, zero compiles, zero execution.",
           rs["hits"], {})
    yield ("trino_tpu_result_cache_misses",
           "Result cache misses (statements executed) since process "
           "start.", rs["misses"], {})
    yield ("trino_tpu_result_cache_evictions_total",
           "Results evicted by the LRU since process start.",
           rs["evictions"], {})
    yield ("trino_tpu_result_cache_invalidations_total",
           "Results dropped by DDL/INSERT table invalidation since "
           "process start.", rs["invalidations"], {})
    ss = scan_cache_stats()
    yield ("trino_tpu_scan_cache_entries",
           "Staged table scans resident across live scan caches.",
           ss["entries"], {})
    yield ("trino_tpu_scan_cache_bytes",
           "Device bytes pinned by staged scan pages.", ss["bytes"], {})
    yield ("trino_tpu_scan_cache_hits",
           "Scan cache hits since process start — table scans served "
           "from staged device pages.", ss["hits"], {})
    yield ("trino_tpu_scan_cache_misses",
           "Scan cache misses (scans staged from the connector) since "
           "process start.", ss["misses"], {})

    from trino_tpu.exec.table_cache import (device_residency,
                                            table_cache_stats)
    ts = table_cache_stats()
    tc = "Device-resident hot-table cache: "
    yield ("trino_tpu_table_cache_entries",
           tc + "promoted (table, columns) working sets resident.",
           ts["entries"], {})
    yield ("trino_tpu_table_cache_bytes",
           tc + "HBM pinned by resident columns.", ts["bytes"], {})
    yield ("trino_tpu_table_cache_hits",
           tc + "scans served entirely from HBM (zero host->device "
           "staging).", ts["hits"], {})
    yield ("trino_tpu_table_cache_misses",
           tc + "scans that staged from the connector.",
           ts["misses"], {})
    yield ("trino_tpu_table_cache_evictions",
           tc + "entries evicted under the byte budget.",
           ts["evictions"], {})
    yield ("trino_tpu_table_cache_promotions",
           tc + "working sets promoted since process start.",
           ts["promotions"], {})
    yield ("trino_tpu_table_cache_invalidations",
           tc + "entries dropped by DDL/INSERT invalidation.",
           ts["invalidations"], {})
    for dev, nbytes in sorted(device_residency().items(),
                              key=lambda kv: -1 if kv[0] is None
                              else kv[0]):
        # None = promoted outside a pinned shard (the default device);
        # a distinct label value so it can never collide with a real
        # device-0 series in the exposition
        yield ("trino_tpu_table_cache_device_bytes",
               tc + "resident bytes attributed per mesh device.",
               nbytes, {"device": "default" if dev is None else dev})

    try:
        from trino_tpu.connector.lake import lake_stats
        ls = lake_stats()
        lk = "Lake connector: "
        yield ("trino_tpu_lake_files_written",
               lk + "data files committed since process start.",
               ls["files_written"], {})
        yield ("trino_tpu_lake_files_scanned",
               lk + "data files read by scans.", ls["files_scanned"], {})
        yield ("trino_tpu_lake_files_pruned",
               lk + "data files skipped by partition/zone-map pruning "
               "against the scan TupleDomain.", ls["files_pruned"], {})
        yield ("trino_tpu_lake_row_groups_pruned",
               lk + "row groups skipped by zone-map pruning.",
               ls["row_groups_pruned"], {})
        yield ("trino_tpu_lake_manifest_commits",
               lk + "atomic manifest swaps committed.",
               ls["manifest_commits"], {})
        yield ("trino_tpu_lake_replayed_commits",
               lk + "write-token replays detected (retried INSERT/CTAS "
               "attempts that no-op'd — the exactly-once proof).",
               ls["replayed_commits"], {})
        yield ("trino_tpu_lake_corruption_detected",
               lk + "read-side content-verification failures (file or "
               "row-group digest mismatch, undecodable file) — each "
               "classified LAKE_DATA_CORRUPTION, never silent wrong "
               "rows.", ls["corruption_detected"], {})
        yield ("trino_tpu_lake_files_quarantined",
               lk + "data files in the per-process corruption "
               "quarantine (fail-fast until lake_fsck clears them).",
               ls["files_quarantined"], {})
    except Exception:   # lake import must never fail the scrape
        pass

    from trino_tpu.exec.sliced.checkpoint import checkpoint_stats
    cs = checkpoint_stats()
    yield ("trino_tpu_checkpoints_saved",
           "Operator checkpoints saved since process start (sliced "
           "execution slice/shard boundaries).", cs["saved"], {})
    yield ("trino_tpu_checkpoints_restored",
           "Operator checkpoints a retry resumed from since process "
           "start (work NOT re-executed).", cs["restored"], {})
    yield ("trino_tpu_checkpoints_dropped",
           "Operator checkpoints released since process start.",
           cs["dropped"], {})

    from trino_tpu.serve.streaming import stream_stats
    st = stream_stats()
    yield ("trino_tpu_streams_open",
           "Result streams currently open (producing or draining).",
           st["open"], {})
    yield ("trino_tpu_stream_buffered_chunks",
           "Result chunks resident in open stream ring buffers "
           "(bounded per stream by the ring size — the backpressure "
           "signal).", st["buffered_chunks"], {})


REGISTRY.register_gauges(_engine_gauges)
