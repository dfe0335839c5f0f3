"""device: the most host memory the engine's process (run.py's, which
holds the server, the executor, XLA's compiler and the TPU runtime's own
host buffers) has held so far, as the kernel counts it: `ru_maxrss` when
the metric is read, after the window. The reference's workers are other
processes and are not in it."""
import resource


def read(ctx):
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9
