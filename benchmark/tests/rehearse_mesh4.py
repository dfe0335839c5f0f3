"""The harness on a four-chip host, once: a rehearsal of the harness, not
a number of the system, and nothing of it is committed as a cell.

    python3 benchmark/tests/rehearse_mesh4.py <directory> [--schema sf1]
        [--seed n] [--seconds s] [--trace 0|1]

Makes the temporary copy `rehearsal.make_copy` makes — the benchmark, and
added to it by new files and appended entries a deployment with
`runner: "mesh"` on four chips and its cell `<schema>-mesh4-power` — in
`<directory>`, and runs that cell through the copy's own run.py, look for
a chip included, in a child process (this one never imports JAX, so the
child gets the chips). The child's lines pass through; the exit code is
the child's.
"""

import argparse
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import rehearsal    # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("directory")
    ap.add_argument("--schema", default="sf1")
    ap.add_argument("--seed", type=int, default=2147483747)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    directory = os.path.abspath(args.directory)
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    copy = rehearsal.make_copy(directory, mesh_schema=args.schema)
    # the copy holds the benchmark alone: the program is this checkout's
    env = dict(os.environ, PYTHONPATH=rehearsal.ROOT)
    return subprocess.run(
        [sys.executable, os.path.join(copy, "benchmark", "run.py"),
         "--workload", f"{args.schema}-mesh4-power",
         "--seed", str(args.seed), "--seconds", str(args.seconds),
         "--trace", str(args.trace)], env=env, cwd=copy).returncode


if __name__ == "__main__":
    sys.exit(main())
