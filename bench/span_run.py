#!/usr/bin/env python3
"""Run one cell traced, as ``run.py --trace 1`` does, and also read the
per-layer metrics of the program's own spans and per-request stamps.

    python3 bench/span_run.py --workload <cell> --seed <n> --seconds <s>

``harness.run`` hands its readers the harness's records and the reduction of
:mod:`bench.trace_reduce` only.  For this one process, this script also
copies each request's ``RequestState.admitted_at`` and ``prefix_matched``
onto its ``ReqRec`` (``harness.window_record``), and reduces the same trace
file with :mod:`bench.engine_trace` into the record's ``engine_trace``
(``harness.reduce_trace``).  The readers of ``METRICS`` then read those.
The last line of standard output is the result, as ``run.py`` prints it,
with the end-to-end metrics of the traced window and the clock offset added;
standard error logs the offset's bracket and the split of the device's idle
time inside ``Engine.step``.
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent

# (name, unit, better, source, layer, moves) of each metric read here
METRICS = (
    ("admit_wait_p90_s", "s", "lower", "program_counter",
     "pool (serving/pool.py EnginePool)", "ttft_p90_s"),
    ("prefix_mapped_share", "%", "higher", "program_counter",
     "prefix index (serving/kvcache.py PrefixIndex)", "ttft_p90_s"),
    ("prefill_span_ms_per_ktok", "ms", "lower", "device_trace",
     "engine prefill (Engine._paged_prefill)", "ttft_p90_s"),
    ("decode_span_ms", "ms", "lower", "device_trace",
     "engine decode (Engine.step)", "itl_mean_ms"),
    ("step_host_idle_ms", "ms", "lower", "device_trace",
     "device", "itl_mean_ms"),
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@contextlib.contextmanager
def engine_records(harness):
    """Wrap ``harness.window_record`` and ``harness.reduce_trace`` so that
    the record carries the program's stamps and ``engine_trace``; yields a
    dict that holds the traced window's end-to-end metrics and the
    :class:`bench.engine_trace.EngineTrace` once the run is over."""
    from bench import engine_trace
    got = {"engine_trace": None}
    saved = harness.window_record, harness.reduce_trace
    window_record, reduce_trace = saved

    def reduce(trace_dir):
        reduced = reduce_trace(trace_dir)
        path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
        got["engine_trace"] = engine_trace.reduce_file(path)
        return reduced

    def record(load, t0, t1, *args, **kwargs):
        rec = window_record(load, t0, t1, *args, **kwargs)
        for rid, r in rec["reqs"].items():
            st = load.states.get(rid)
            r.admitted_at = getattr(st, "admitted_at", None)
            r.prefix_matched = getattr(st, "prefix_matched", 0)
        rec["engine_trace"] = got["engine_trace"]
        got["e2e"] = harness.end_to_end(load, t0, t1, 0.0)
        del got["e2e"]["setup_s"]            # not a set-up run
        return rec

    harness.window_record, harness.reduce_trace = record, reduce
    try:
        yield got
    finally:
        harness.window_record, harness.reduce_trace = saved


def log_engine_trace(tr) -> dict:
    """Log the clock offset's brackets, the shift taken and the idle split
    at it and at both ends of the bracket; returns them."""
    lo, hi = tr.bracket
    out = {"paired": tr.paired, "lo_ns": tr.lo, "hi_ns": tr.hi,
           "lo_rt_ns": tr.lo_rt, "hi_rt_ns": tr.hi_rt, "delta_ns": tr.delta,
           "steps": len(tr.named("engine.step"))}
    log(f"[trace] device clock offset lo={tr.lo} hi={tr.hi} ns over "
        f"{tr.paired} paired decode steps; runtime events lo={tr.lo_rt} "
        f"hi={tr.hi_rt} ns; "
        + (f"shift {tr.delta} ns" if tr.aligned
           else "empty bracket, nothing shifted"))
    if tr.aligned:
        for key, d in (("", tr.delta), ("_lo", lo), ("_hi", hi)):
            split = out[f"idle_by_span_s{key}"] = tr.idle_by_span(d)
            log(f"[trace] device idle inside engine.step at shift {d} ns, "
                "by innermost span (s): " + ", ".join(
                    f"{k}={v:.6f}" for k, v in sorted(
                        split.items(), key=lambda kv: -kv[1])))
    return out


def run(cell_name: str, seed: int, seconds: float, root: Path = ROOT,
        require_tpu: bool = True) -> dict:
    from bench import harness, spec
    cell = spec.load_cell(cell_name, root)
    extra = tuple(spec.Metric(*m) for m in METRICS)
    cell = dataclasses.replace(cell, per_layer=cell.per_layer + extra)
    with engine_records(harness) as got:
        result = harness.run(cell, seed, seconds, True, PROCESS_START,
                             require_tpu=require_tpu, root=root)
    result["traced_end_to_end"] = got["e2e"]
    if got["engine_trace"] is not None:
        result["clock_offset"] = log_engine_trace(got["engine_trace"])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    harness.use_compile_cache(ROOT)
    result = run(args.workload, args.seed, args.seconds)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
