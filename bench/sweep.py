#!/usr/bin/env python3
"""Find an open-loop cell's knee: serve its mix at several fixed rates, one
window each, in one process on one chip, and print each window's end-to-end
metrics and the backlog it left.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2,3,4,5

The knee is the highest rate whose backlog does not grow through the
window; a cell's traffic file then fixes its rate at a share of it.  This
is a tool for defining cells, not a run of the benchmark.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, spec, stats, traffic
    cell = spec.load_cell(args.workload, ROOT)
    if cell.traffic["loop"] != "open":
        print("sweep.py: the cell's mix is not an open loop", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT)
    import jax
    try:
        harness.devices_for(cell.chips, require_tpu=True)
    except harness.NoAccelerator as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 3
    peaks = harness.load_peak(jax.devices()[0].device_kind, ROOT)
    cfg, a, backend = harness.build(cell, args.seed)
    engines = list(backend.pool.engines)
    page = engines[0].page_size
    driver = harness.Driver(backend.pool, cfg.name, cell.traffic, args.seed,
                            cfg.vocab_size, page)
    harness.warm_up(driver, engines, args.seed)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, arrival=dict(cell.traffic["arrival"],
                                              rate_per_s=rate))
        driver = harness.Driver(backend.pool, cfg.name, mix, args.seed + k,
                                cfg.vocab_size, page)
        sched = traffic.open_schedule(mix, args.seconds)
        t_start = time.monotonic()
        t0 = t_start + float(mix["preroll_s"])
        t1 = t0 + args.seconds
        driver.run(t_start, t0, t1, schedule=sched)
        out = harness.end_to_end(driver, t0, t1, 0.0)
        out.pop("setup_s")
        waits = [(min(r.admit_step, t1) if r.admit_step is not None else t1)
                 - r.due for r in driver.reqs.values() if t0 <= r.due < t1]
        out.update(rate=rate,
                   backlog_at_close=sum(len(e.waiting) for e in engines),
                   active_at_close=sum(len(e.active) for e in engines),
                   queue_wait_p50_s=stats.median(waits),
                   queue_wait_p90_s=stats.nearest_rank(waits, 0.9),
                   attempted=len(waits))
        # the per-layer metrics that need no trace
        rec = harness.window_record(driver, t0, t1, a, engines[0].n_slots,
                                    peaks)
        for m in cell.per_layer:
            v = spec.metric_reader(m.name, ROOT)(rec)
            if v is not None:
                out[m.name] = v
        print(json.dumps(out), flush=True)
        for eng in engines:                    # drain, unrecorded
            while eng.waiting or eng.active:
                eng.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
