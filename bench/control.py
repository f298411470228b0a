#!/usr/bin/env python3
"""Readings that the correctness limits are set from: the program's, and the
lower-precision control's, on many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

Each seed is a whole run of the cell (set-up, pre-roll, a window of
``--seconds`` at the cell's load) whose sampled finished requests are run
through the float32 reference twice: once to read the gap of each served
token (the program's reading), once to read the gap of the token that the
reference computed with float8 weights and bfloat16 activations puts first
at the same positions (the control's reading).  Both are judged by the
cell's own limits: ``correct`` is the program's verdict and
``control_correct`` the control's.  One JSON line per seed.
The benchmark's own runs never make the control's reading.
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, spec
    cell = spec.load_cell(args.workload, ROOT)
    harness.use_compile_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            r = harness.run(cell, seed, args.seconds, False, time.monotonic(),
                            root=ROOT, control=True)
        except harness.NoAccelerator as e:
            print(f"control.py: {e}", file=sys.stderr)
            return 3
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "control_correct": r["control_correct"],
                          "attempted": r["attempted"], **r["readings"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
