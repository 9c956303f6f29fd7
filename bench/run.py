"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (model configuration, traffic mix, chips) is looked up by name in
``BENCHMARK.json``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window.  The last line of standard output is one JSON
object; the numbers compared for ``correct`` are also the last lines of
standard error.  Without the TPU chips the cell asks for, it prints no
result and exits 1.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
# libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes only
# inside its checkout and its own HOME, XDG_CACHE_HOME and TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    spec = harness.cell_spec(args.workload)
    out = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START)
    if out is None:
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
