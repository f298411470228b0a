#!/usr/bin/env python3
"""Record ``data/v5e_engine_steps.xplane.pb``: a few steps of a paged engine
under the profiler, inside a harness ``window`` span.

    python3 bench/tests/record_engine_trace.py <out.xplane.pb>

The engine serves qwen2-1.5b's widths cut to two layers, 4 slots of 256
positions in pages of 16, with the fused decode kernel (compiled on a TPU).
A warm-up compiles every step shape and leaves one 48-token prompt's pages
in the prefix index.  In the window three requests arrive together: one
that maps those three pages, one fresh, and one of a single new token, so
the trace holds prefills with and without a prefix hit, a retirement at
admission, and decode steps.  Profiler options as the harness's
(``harness.start_trace``).
"""
import dataclasses
import glob
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402

from bench import harness  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serving.engine import Engine, Request  # noqa: E402

PREFIX = [(7 * i) % 500 + 1 for i in range(48)]


def main(out: str) -> int:
    cfg = dataclasses.replace(get_config("qwen2-1.5b"), n_layers=2)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, n_slots=4, max_seq_len=256, page_size=16,
                 max_prefill_chunk=64)
    top = max(eng._chunk_sizes)
    for rid, prompt in enumerate((PREFIX + [1], list(range(1, top + 1)),
                                  list(range(2, top + 1)))):
        eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=3))
    eng.run_until_drained()
    d = tempfile.mkdtemp(prefix="engine-trace-")
    harness.start_trace(d)
    with jax.profiler.TraceAnnotation("window"):
        eng.submit(Request(rid=10, prompt=PREFIX + [2, 3], max_new_tokens=6))
        eng.submit(Request(rid=11, prompt=list(range(3, 40)),
                           max_new_tokens=3))
        eng.submit(Request(rid=12, prompt=[5, 6, 7], max_new_tokens=1))
        eng.run_until_drained()
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0], out)
    shutil.rmtree(d, ignore_errors=True)
    print(f"{out}: {eng.steps} steps, prefix hits {eng.prefix_hits}, "
          f"fused kernel {eng.use_paged_kernel}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
