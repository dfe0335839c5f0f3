"""Lightweight trace spans: query -> fragment -> operator.

Reference parity: the reference engine emits OpenTelemetry spans from
`Trace`-annotated scopes (io.opentelemetry wiring in trino-main's
ServerMainModule); here a span is a plain host-side record — name, kind,
start/end, attributes, children — cheap enough to record on every query,
and the structured JSON dump replaces the OTLP exporter (QueryInfo.trace /
the event payload carry it per query).

One clock: every stamp is `time.monotonic()`, the clock the server stamps
a submitted query with (`_Query.started`) and the one a device trace is
tied to by whoever starts the profiler (benchmark/run.py stamps it at its
slice annotation), so spans of concurrent queries and device events lie
on one axis. The absolute stamps stay on the Span; only the JSON dump is
relative.

Spans are built single-threaded by the owning query's executor thread
(the same contract as FaultInjector); readers only see the dump taken at
query end.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Span:
    name: str
    kind: str = "internal"     # query | phase | fragment | exchange | operator
    start_s: float = dataclasses.field(default_factory=time.monotonic)
    end_s: Optional[float] = None
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    children: List["Span"] = dataclasses.field(default_factory=list)

    def finish(self) -> "Span":
        if self.end_s is None:
            self.end_s = time.monotonic()
        return self

    @property
    def wall_s(self) -> float:
        end = self.end_s if self.end_s is not None else time.monotonic()
        return max(0.0, end - self.start_s)

    def to_json(self) -> Dict[str, Any]:
        """Structured dump; times are relative to the span's own start so
        the tree is self-contained (a monotonic origin means nothing in
        another process)."""
        return self._to_json(self.start_s)

    def _to_json(self, origin: float) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "name": self.name,
            "kind": self.kind,
            "start_ms": round((self.start_s - origin) * 1000, 3),
            "wall_ms": round(self.wall_s * 1000, 3),
        }
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        if self.children:
            out["children"] = [c._to_json(origin) for c in self.children]
        return out


def to_chrome_trace(dump: Dict[str, Any],
                    query_id: str = "") -> Dict[str, Any]:
    """Serialize a structured span dump (Span.to_json / QueryInfo.trace)
    as Chrome-trace JSON — the `traceEvents` object format Perfetto and
    chrome://tracing open directly.

    Mapping: every span becomes one complete event (`ph: "X"`) with
    microsecond `ts`/`dur` relative to the query root. The span tree
    flattens onto tracks (`tid`): the query/phase/fragment/exchange
    hierarchy nests by time containment on the main track, while
    synthesized operator spans — which all start at the root origin and
    would overlap — each get their own track so per-operator walls render
    side by side. Span attrs ride in `args` verbatim.
    """
    events = [
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
         "args": {"name": f"trino_tpu query {query_id}".strip()}},
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
         "args": {"name": "query"}},
    ]
    op_tid = [100]

    def walk(span: Dict[str, Any]) -> None:
        kind = span.get("kind", "internal")
        if kind == "operator":
            tid = op_tid[0]
            op_tid[0] += 1
            events.append(
                {"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
                 "args": {"name": f"operator {span['name']}"}})
        else:
            tid = 1
        event: Dict[str, Any] = {
            "name": str(span.get("name", "")),
            "cat": str(kind),
            "ph": "X",
            "ts": float(span.get("start_ms", 0.0)) * 1000.0,
            "dur": float(span.get("wall_ms", 0.0)) * 1000.0,
            "pid": 1,
            "tid": tid,
        }
        attrs = span.get("attrs")
        if attrs:
            event["args"] = {str(k): v if isinstance(
                v, (int, float, bool, str, type(None))) else str(v)
                for k, v in attrs.items()}
        events.append(event)
        for child in span.get("children", ()) or ():
            walk(child)

    if dump:
        walk(dump)
    return {"displayTimeUnit": "ms", "traceEvents": events}
