#!/usr/bin/env python3
"""Run a configuration that is not a cell yet, as the benchmark would run it
as one, on several seeds in one process.

    python3 bench/trial.py --config mixtral-8x7b-ep4-l4 --mix long-prompt \\
        --seeds 1,2,3 --seconds 20 [--control]

The configuration is ``bench/tests/configs/<config>.json``, the mix
``bench/tests/mixes/<mix>.json`` and the limits
``bench/tests/limits/<config>.<mix>.json``.  They are added, as files and a
``workloads`` entry, to a copy of the benchmark in a temporary directory
(``bench/tests/tiny.make_tree``), and each seed is a whole ``harness.run`` of
that cell: set-up, pre-roll, a window of ``--seconds``, the check.
``--control`` also judges the lower-precision control on every seed.  The
compile cache is the benchmark's (``harness.use_compile_cache``).  One JSON
line per seed; exits 3 without the chips.
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
TESTS = ROOT / "bench" / "tests"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, spec
    from bench.tests import tiny

    def load(*parts):
        return json.loads(TESTS.joinpath(*parts).read_text())

    config = load("configs", f"{args.config}.json")
    cell_name = f"{args.config}.{args.mix}"
    chips = int(config["chips"])
    cache = harness.use_compile_cache(ROOT)
    harness.log(f"[trial] {cell_name} on {chips} chips, compile cache {cache}")
    tree = Path(tempfile.mkdtemp(prefix="bench-trial-"))
    try:
        tiny.make_tree(tree, config=config,
                       mix=load("mixes", f"{args.mix}.json"),
                       limits=load("limits", f"{cell_name}.json"),
                       cell=cell_name, chips=chips)
        cell = spec.load_cell(cell_name, tree)
        start = PROCESS_START
        for seed in (int(s) for s in args.seeds.split(",")):
            try:
                r = harness.run(cell, seed, args.seconds, False, start,
                                root=tree, control=args.control)
            except harness.NoAccelerator as e:
                print(f"trial.py: {e}", file=sys.stderr)
                return 3
            print(json.dumps({"seed": seed, "cache": cache, **r}), flush=True)
            start = time.monotonic()
    finally:
        shutil.rmtree(tree, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
