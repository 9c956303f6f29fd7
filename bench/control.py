"""Judge the program and the float8 control over many seeds.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1,2,3

One process, one chip.  For each seed it runs the cell as the benchmark
does, at the cell's own load, then judges two things over the same sample
of served requests with the same limits: the program's tokens, and, in the
program's place, the tokens that a float8 computation of the reference puts
first.  It prints one line per seed with both verdicts and the numbers
compared; the control has to come out not correct.  The limits in
``bench/traffic/*.json`` were set from its output: above the program's
largest reading, below the control's smallest.  The benchmark's own runs
never call this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length; the benchmark's run_seconds")
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    spec = harness.cell_spec(args.workload)
    seconds = args.seconds or harness.load_benchmark()["run_seconds"]
    for seed in (int(x) for x in args.seeds.split(",")):
        out = harness.run(spec, seed, seconds, False, control=True,
                          t_start=time.perf_counter())
        if out is None:
            return 1
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "control_correct": out["control"]["correct"],
                          "control_checks": out["control"]["checks"],
                          "attempted": out["attempted"],
                          "metrics": out["metrics"], **out["info"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
