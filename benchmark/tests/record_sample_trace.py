"""Records `trace_sample.xplane.pb`, the small trace that
test_trace_reduce.py checks `trace_reduce.py` on. Run once on the chip:

    python3 benchmark/tests/record_sample_trace.py <output directory>

Three bursts of a jitted reduction with sleeps between them, inside the
slice annotations; the "requests" laid over it are printed as JSON so the
test can keep them beside the file.
"""

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce  # noqa: E402


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_sample_trace: needs a TPU")
    step = jax.jit(lambda x: jnp.sort(x * 2 + 1).sum())
    x = jnp.arange(1 << 20, dtype=jnp.int32)
    step(x).block_until_ready()
    trace_dir = os.path.join(out_dir, "sample_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    requests = []
    t_begin = time.monotonic()
    with jax.profiler.TraceAnnotation(trace_reduce.BEGIN):
        pass
    for shape in ("a", "b", "a"):
        time.sleep(0.02)                    # idle, between requests
        t0 = time.monotonic()
        for _ in range(4):
            step(x).block_until_ready()
            time.sleep(0.002)               # idle, inside a request
        requests.append({"shape": shape, "t_send": t0,
                         "t_done": time.monotonic()})
    time.sleep(0.02)
    with jax.profiler.TraceAnnotation(trace_reduce.END):
        pass
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(trace_dir)
    shutil.copy(path, os.path.join(out_dir, "trace_sample.xplane.pb"))
    reduced = trace_reduce.reduce_xplane(path, requests, t_begin)
    with open(os.path.join(out_dir, "trace_sample.json"), "w") as f:
        json.dump({"t_begin": t_begin, "requests": requests,
                   "reduced": reduced}, f, indent=1)
    print(json.dumps(reduced))
    print(os.path.getsize(path), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
