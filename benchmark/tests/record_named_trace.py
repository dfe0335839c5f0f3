"""Records `trace_named_sample.xplane.pb`, the small trace of the ENGINE
that test_trace_programs.py checks `trace_programs.py` on. Run once on the
chip:

    python3 benchmark/tests/record_named_trace.py <output directory>

TPC-H q3 at `tiny` through `LocalQueryRunner`, twice inside the slice
annotations with a sleep between (warm: the compiles are outside), as if
each were a served request: the query's own `stats.spans` and the
begin annotation's `time.monotonic()` are kept beside the file as JSON,
with what `trace_programs.reduce` made of it on the day. The plane
`/host:metadata` (every module's HLO proto, 1 MB of the 1.7) is left out
of the kept copy: nothing here reads it.
"""

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for path in (BENCH, os.path.dirname(BENCH)):
    sys.path.insert(0, path)

import trace_programs  # noqa: E402
import trace_reduce  # noqa: E402

Q3 = """
SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue,
       o_orderdate, o_shippriority
FROM customer, orders, lineitem
WHERE c_mktsegment = 'BUILDING' AND c_custkey = o_custkey
  AND l_orderkey = o_orderkey AND o_orderdate < DATE '1995-03-15'
  AND l_shipdate > DATE '1995-03-15'
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate LIMIT 10
"""


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def copy_without_planes(src: str, dst: str, drop=("/host:metadata",)):
    """The XSpace of `src` with the named planes left out: top-level
    fields copied byte for byte, so every other plane is untouched."""
    with open(src, "rb") as f:
        data = memoryview(f.read())
    out = bytearray()
    for number, wire, value in trace_programs._fields(data):
        if wire != 2:
            raise ValueError(f"XSpace field {number}: wire type {wire}")
        name = next((trace_programs._text(v)
                     for n, _, v in trace_programs._fields(value)
                     if n == 2), "") if number == 1 else ""
        if name in drop:
            continue
        out += _varint(number << 3 | 2) + _varint(len(value)) + value
    with open(dst, "wb") as f:
        f.write(out)


def main(out_dir: str) -> int:
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_named_trace: needs a TPU")
    from trino_tpu.exec import LocalQueryRunner
    runner = LocalQueryRunner.tpch("tiny")
    runner.session.set("result_cache_enabled", False)
    for _ in range(2):                      # compile, then warm
        rows = runner.execute(Q3).rows
    trace_dir = os.path.join(out_dir, "named_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    spans = []
    t_begin = time.monotonic()
    with jax.profiler.TraceAnnotation(trace_reduce.BEGIN):
        pass
    for _ in range(2):
        time.sleep(0.02)                    # idle, no request
        queued_at = time.monotonic()
        time.sleep(0.005)                   # "queued": nobody runs it yet
        assert runner.execute(Q3, queued_at=queued_at,
                              dequeued_at=time.monotonic()).rows == rows
        spans.append(runner.last_query_stats["spans"])
    time.sleep(0.02)
    with jax.profiler.TraceAnnotation(trace_reduce.END):
        pass
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(trace_dir)
    kept = os.path.join(out_dir, "trace_named_sample.xplane.pb")
    copy_without_planes(path, kept)
    reduced = trace_programs.reduce(kept, spans, t_begin)
    with open(os.path.join(out_dir, "trace_named_sample.json"), "w") as f:
        json.dump({"t_begin": t_begin, "spans": spans, "reduced": reduced},
                  f, indent=1)
    print(json.dumps(reduced, indent=1))
    print(os.path.getsize(path), "bytes,", os.path.getsize(kept), "kept")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
