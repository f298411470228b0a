#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the repository root.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics and the device's busy time from a
profiler trace of the window.  The last line of standard output is the
result, one JSON object; the numbers the correctness check compared are the
last lines of standard error.  Exits non-zero and prints no result when JAX
finds no TPU or fewer chips than the cell asks for, or when the system
under test (``src/repro``) is not there.  JAX's persistent compilation cache
is kept in ``<root>/.jax_cache`` (``harness.use_compile_cache``).
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: no system under test at {ROOT / 'src' / 'repro'}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, spec
    try:
        cell = spec.load_cell(args.workload, ROOT)
    except spec.SpecError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             PROCESS_START, root=ROOT)
    except harness.NoAccelerator as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 3
    except Exception:  # noqa: BLE001 — a failed run prints no result
        traceback.print_exc()
        return 1
    import json
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
