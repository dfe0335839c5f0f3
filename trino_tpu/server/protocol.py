"""Client protocol JSON shaping for /v1/statement.

Reference parity: client/trino-client QueryResults.java:38 + Column.java +
StatementClientV1.java:61 — the exact JSON field names and value encodings
the stock Trino CLI/JDBC driver expects, so they can speak to this engine
unmodified: `id`, `columns` (name + type + typeSignature), `data` as row
arrays, `nextUri` paging, `stats.state`, and `error.failureInfo`.

Value encoding follows client/trino-client's typed deserialization: dates
and timestamps as ISO strings, decimals as plain decimal strings, doubles
as JSON numbers, varchar as strings.
"""

from __future__ import annotations

import datetime
import decimal
from typing import Any, Dict, List, Optional, Sequence

from trino_tpu import types as T


def type_signature(typ: T.Type) -> Dict[str, Any]:
    display = typ.display()
    raw = display.split("(")[0]
    arguments: List[Dict[str, Any]] = []
    if isinstance(typ, T.DecimalType):
        arguments = [{"kind": "LONG", "value": typ.precision},
                     {"kind": "LONG", "value": typ.scale}]
    elif isinstance(typ, T.VarcharType):
        length = getattr(typ, "length", None)
        arguments = [{"kind": "LONG",
                      "value": length if length is not None else 2147483647}]
    return {"rawType": raw, "arguments": arguments}


def columns_json(names: Sequence[str],
                 types: Sequence[T.Type]) -> List[Dict[str, Any]]:
    return [{"name": n, "type": t.display(), "typeSignature":
             type_signature(t)} for n, t in zip(names, types)]


def encode_value(value: Any, typ: T.Type) -> Any:
    if value is None:
        return None
    if isinstance(typ, T.DateType):
        return value.isoformat()
    if isinstance(typ, T.TimestampType):
        if isinstance(value, datetime.datetime):
            return value.strftime("%Y-%m-%d %H:%M:%S.%f")[:-3]
        return str(value)
    if isinstance(typ, T.DecimalType):
        if isinstance(value, decimal.Decimal):
            return format(value, "f")
        return str(value)
    if isinstance(typ, (T.DoubleType, T.RealType)):
        return float(value)
    if isinstance(typ, T.BooleanType):
        return bool(value)
    if isinstance(typ, (T.VarcharType, T.CharType)):
        return str(value)
    return int(value)


def encode_rows(rows: Sequence[Sequence[Any]],
                types: Sequence[T.Type]) -> List[List[Any]]:
    return [[encode_value(v, t) for v, t in zip(row, types)]
            for row in rows]


def error_json(message: str, error_name: str = "GENERIC_USER_ERROR",
               error_code: int = 0,
               error_type: str = "USER_ERROR") -> Dict[str, Any]:
    """QueryError.java shape (failureInfo = FailureInfo.java)."""
    return {
        "message": message,
        "errorCode": error_code,
        "errorName": error_name,
        "errorType": error_type,
        "failureInfo": {"type": error_name, "message": message,
                        "suppressed": [], "stack": []},
    }


def error_from_exception(exc: BaseException) -> Dict[str, Any]:
    """QueryError from the engine taxonomy (trino_tpu/errors.py): the
    wire errorName/errorCode/errorType come from classify, so the client
    sees EXCEEDED_TIME_LIMIT / USER_CANCELED / SYNTAX_ERROR instead of a
    Python class name."""
    from trino_tpu.errors import classify
    code = classify(exc)
    return error_json(f"{type(exc).__name__}: {exc}",
                      error_name=code.name, error_code=code.code,
                      error_type=code.type)


def warning_json(message: str, code: int = 1,
                 name: str = "MEMORY_LEAK") -> Dict[str, Any]:
    """TrinoWarning.java shape (warningCode is a nested code+name)."""
    return {"warningCode": {"code": code, "name": name},
            "message": message}


def stats_json(state: str, *, queued: bool = False, done: bool = False,
               rows: int = 0, elapsed_ms: int = 0, queued_ms: int = 0,
               peak_memory_bytes: int = 0,
               cpu_time_ms: Optional[int] = None,
               processed_bytes: int = 0,
               spilled_bytes: int = 0) -> Dict[str, Any]:
    """StatementStats.java — the CLI renders progress from these fields.
    cpu/bytes/spill come from the query's stats collector (obs/stats.py)
    when the server has them. Times as the reference means them:
    elapsed runs from the submit, queued is the part of it spent waiting
    for an executor thread, and wall (cpuTimeMillis' fallback too) is
    what is left — the execution."""
    queued_ms = min(max(int(queued_ms), 0), int(elapsed_ms))
    running_ms = elapsed_ms - queued_ms
    return {
        "state": state,
        "queued": queued,
        "scheduled": not queued,
        "nodes": 1,
        "totalSplits": 1,
        "queuedSplits": 1 if queued else 0,
        "runningSplits": 0,
        "completedSplits": 0 if queued else 1,
        "cpuTimeMillis": running_ms if cpu_time_ms is None else cpu_time_ms,
        "wallTimeMillis": running_ms,
        "queuedTimeMillis": queued_ms,
        "elapsedTimeMillis": elapsed_ms,
        "processedRows": rows,
        "processedBytes": processed_bytes,
        "physicalInputBytes": 0,
        "peakMemoryBytes": peak_memory_bytes,
        "spilledBytes": spilled_bytes,
    }


def query_results(query_id: str, base_uri: str, *,
                  columns: Optional[List[Dict[str, Any]]] = None,
                  data: Optional[List[List[Any]]] = None,
                  next_uri: Optional[str] = None,
                  state: str = "RUNNING",
                  error: Optional[Dict[str, Any]] = None,
                  update_type: Optional[str] = None,
                  rows: int = 0,
                  elapsed_ms: int = 0,
                  queued_ms: int = 0,
                  peak_memory_bytes: int = 0,
                  cpu_time_ms: Optional[int] = None,
                  processed_bytes: int = 0,
                  spilled_bytes: int = 0,
                  warnings: Optional[List[Dict[str, Any]]] = None
                  ) -> Dict[str, Any]:
    out: Dict[str, Any] = {
        "id": query_id,
        "infoUri": f"{base_uri}/ui/query.html?{query_id}",
        "stats": stats_json(state, queued=(state == "QUEUED"),
                            done=next_uri is None, rows=rows,
                            elapsed_ms=elapsed_ms, queued_ms=queued_ms,
                            peak_memory_bytes=peak_memory_bytes,
                            cpu_time_ms=cpu_time_ms,
                            processed_bytes=processed_bytes,
                            spilled_bytes=spilled_bytes),
        "warnings": warnings or [],
    }
    if next_uri is not None:
        out["nextUri"] = next_uri
    if columns is not None:
        out["columns"] = columns
    if data:
        out["data"] = data
    if error is not None:
        out["error"] = error
    if update_type is not None:
        out["updateType"] = update_type
    return out
