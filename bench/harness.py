"""Set up one cell, drive its traffic through the served path, measure, and
check what the timed path produced against the plain reference.

Set-up follows the program's normal path: ``repro.configs.get_config`` with
the configuration file's overrides; seeded weights made on the device
(:mod:`bench.weights`); ``JaxBackend`` and ``apply_plan`` of a one-group
``Plan`` whose hardware, tensor parallelism and replica count the
configuration file's ``engine`` section states, which builds each replica's
paged engine through ``engine_for_group``.  Warm-up serves, on every
replica, requests of random tokens whose prompt length takes every prefill
chunk size, so every step program is compiled (or loaded from the
persistent cache) before the window; then the mix is served for its
pre-roll, and the window opens.

In the window each request is submitted at its due time through
``EnginePool.submit``, and each busy engine of ``EnginePool.engines`` is
stepped, as the loop of ``EnginePool.run_until_drained`` does.  The host
spans ``submit``, ``engine_step`` and ``wait_arrival`` and the ``window``
span go to the profiler when the run is traced.

After the window the program's device state is freed and a sample of the
finished requests is run through the float32 reference: the number
compared is the widest gap by which a served token's logit lies below the
reference's best logit at its position.  For a mixture of experts whose
limits give a ``router_tie_margin`` δ, a position where the reference's
k-th and (k+1)-th router logits lie within δ of each other is a tie, which
the program's rounding may resolve either way: ties are left out of the
widest gap and counted in ``router_tie_share`` (:func:`judge`).

A cell that serves across chips draws its weights, and runs the reference,
split over those chips (:func:`bench.weights.placement`).
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import spec, stats, traffic
from bench.clock import CompileClock, peak_bytes

WARMUP_NEW = 4


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------- #
# set-up
# --------------------------------------------------------------------------- #
def use_compile_cache(root: Path = spec.ROOT) -> str:
    """The benchmark's one rule for JAX's persistent compilation cache, called
    before anything compiles: on, at the fixed ``<root>/.jax_cache``, for
    every program whatever its compile time, so that only a checkout's first
    run of a cell compiles.  A cell across chips keeps it too: a tensor- and
    expert-parallel Mixtral engine on four v5e chips loaded all its programs
    from it and served correctly (jax 0.9.0), where sharded executables from
    the cache had once halted the chip.  Returns the directory."""
    import jax
    # the program keeps its cache where this variable says
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def program_config(cell: spec.Cell):
    from repro.configs import get_config
    rc = cell.config["repro"]
    return dataclasses.replace(get_config(rc["arch"]), **rc.get("overrides", {}))


def check_config(cfg, a) -> None:
    """The program's configuration states the same model as the file's
    published keys, or the run stops before it starts."""
    pairs = {"d_model": a.d, "n_layers": a.layers, "n_heads": a.heads,
             "n_kv_heads": a.kv_heads, "d_head": a.head_dim, "d_ff": a.ffn,
             "vocab_size": a.vocab, "n_experts": a.experts, "top_k": a.top_k,
             "tie_embeddings": a.tied, "qkv_bias": a.qkv_bias,
             "norm_eps": a.eps, "rope_theta": a.theta}
    bad = {k: (getattr(cfg, k), v) for k, v in pairs.items()
           if getattr(cfg, k) != v}
    if bad:
        raise spec.SpecError(f"program config departs from the file "
                             f"(program, file): {bad}")


def replica_group(cell: spec.Cell, model: str):
    """The one replica group the configuration file's ``engine`` section
    states: ``hardware`` (a row of the program's hardware table), ``tp``
    and ``replicas`` of ``slots`` each.  Its devices are the cell's chips."""
    from repro.core.plan import ReplicaGroup
    ec = cell.config["engine"]
    group = ReplicaGroup(model, str(ec["hardware"]), tp=int(ec["tp"]),
                         batch=int(ec["slots"]), count=int(ec["replicas"]))
    if group.devices != cell.chips:
        raise spec.SpecError(f"{cell.config_name}: tp {group.tp} x "
                             f"{group.count} replicas is {group.devices} "
                             f"chips, the cell asks for {cell.chips}")
    return group


def chip_devices(n: int) -> List:
    """The first ``n`` devices in id order, the order in which the pool's
    allocator carves them."""
    import jax
    return sorted(jax.devices(), key=lambda d: d.id)[:n]


def build(cell: spec.Cell, seed: int):
    """The program's served path for the cell, with the seed's weights."""
    import jax

    from bench import weights
    from bench.reference.model import arch
    from repro.core.plan import Plan
    from repro.models import lm
    from repro.serving.backend import JaxBackend

    cfg = program_config(cell)
    a = arch(cell.config)
    check_config(cfg, a)
    eng_cfg = cell.config["engine"]
    group = replica_group(cell, cfg.name)
    # drawn across the chips of the first replica (a replica of one chip
    # takes the one-device draw)
    params = weights.make_program_params(a, seed, chip_devices(group.tp))
    weights.check_layout(params, jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0))))
    backend = JaxBackend(cfg, params, max_seq_len=int(eng_cfg["max_seq_len"]),
                         slots_cap=int(eng_cfg["slots"]),
                         max_replicas_per_group=group.count)
    # the backend holds the served copy, in the compute dtype; the float32
    # tree goes before the engines and their page pools are built
    del params
    backend.apply_plan(Plan((group,)), None)
    engines = backend.pool.engines
    if len(engines) != group.count:
        raise spec.SpecError(f"plan built {len(engines)} engines, the file "
                             f"states {group.count} replicas")
    for eng in engines:
        if eng.page_size != int(eng_cfg["page_size"]) or not eng.paged:
            raise spec.SpecError(f"engine is paged={eng.paged} with page "
                                 f"{eng.page_size}, file says "
                                 f"{eng_cfg['page_size']}")
    return cfg, a, backend


# --------------------------------------------------------------------------- #
# the loop the window drives
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class ReqRec:
    rid: int
    due: float                     # host monotonic seconds
    prompt_len: int
    prefix: int
    output_len: int
    refused: bool = False
    admit_step: Optional[float] = None
    first: Optional[float] = None
    finish: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class StepRec:
    start: float
    end: float
    admitted: List[int]            # rids
    matched: int                   # prompt tokens mapped from the prefix index
    # (prompt length, tokens mapped from the index) of each admission: the
    # step's total is known, and is handed out in admission order, each
    # request taking at most its prompt's whole pages short of its last token
    shares: List[tuple]
    last_first: Optional[float]    # first-token time of the last admission
    decode_lens: List[int]         # context length of each decoded slot


class Driver:
    """Submits a schedule through the pool and steps its engines,
    recording every request, step and token time."""

    def __init__(self, pool, model: str, mix: dict, seed: int, vocab: int,
                 page: int):
        import jax
        self.jax = jax
        self.pool, self.model = pool, model
        self.seed, self.vocab, self.page = seed, vocab, page
        self.prefixes = traffic.prefix_tokens(mix, seed, vocab)
        self.reqs: Dict[int, ReqRec] = {}
        self.steps: List[StepRec] = []
        self.states: Dict[int, object] = {}       # rid -> RequestState
        self.client_of: Dict[int, int] = {}
        self._next_rid = 1_000_000

    def span(self, name: str):
        return self.jax.profiler.TraceAnnotation(name)

    # ------------------------------------------------------------------ #
    def submit(self, prompt: List[int], output_len: int, due: float,
               prefix: int = -1, engine=None) -> int:
        """Submit through the pool, or straight to ``engine`` (warm-up)."""
        from repro.serving.engine import Request
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new_tokens=output_len,
                      arrival_time=due)
        with self.span("submit"):
            if engine is None:
                ok = self.pool.submit(self.model, req)
            else:
                engine.submit(req)
                ok = True
        self.reqs[rid] = ReqRec(rid, due, len(prompt), prefix, output_len,
                                refused=not ok)
        return rid

    def submit_req(self, r: traffic.Req, due: float) -> int:
        return self.submit(traffic.prompt(r, self.seed, self.vocab,
                                          self.prefixes),
                           r.output_len, due, r.prefix)

    def step(self, eng) -> List[object]:
        """One ``Engine.step``; returns the states it finished."""
        before = {st.request.rid: len(st.generated)
                  for st in eng.active.values()}
        n_fin = len(eng.finished)
        matched0 = eng.prefix_index.tokens_matched
        t0 = time.monotonic()
        with self.span("engine_step"):
            eng.step()
        t1 = time.monotonic()
        finished = eng.finished[n_fin:]
        admitted, decode_lens = [], []
        last_first = None
        for st in list(eng.active.values()) + finished:
            rid = st.request.rid
            rec = self.reqs.get(rid)
            if rec is None:
                continue
            new = len(st.generated) - before.get(rid, 0)
            if rid not in before:                 # admitted in this step
                admitted.append(rid)
                self.states[rid] = st
                rec.admit_step, rec.first = t0, st.first_token_time
                rec.times.append(st.first_token_time)
                last_first = max(last_first or 0.0, st.first_token_time)
                new -= 1
            if new > 0:                           # decoded in this step
                decode_lens.append(st.position)
                rec.times.extend([t1] * new)
            if st.finish_time is not None:
                rec.finish = st.finish_time
        matched = eng.prefix_index.tokens_matched - matched0
        left, shares = matched, []
        for rid in admitted:
            rec = self.reqs[rid]
            cap = (rec.prompt_len - 1) // self.page * self.page
            m = min(left, cap) if rec.prefix >= 0 else 0
            left -= m
            shares.append((rec.prompt_len, m))
        self.steps.append(StepRec(t0, t1, admitted, matched, shares,
                                  last_first, decode_lens))
        return finished

    # ------------------------------------------------------------------ #
    def run(self, start: float, t_open: float, t_end: float,
            schedule: Optional[List[traffic.Req]] = None,
            clients: int = 0, pool_reqs: Optional[List[traffic.Req]] = None,
            on_open=None) -> float:
        """Serve from ``start`` until ``t_end`` (host monotonic seconds).
        Open loop: ``schedule`` with due times relative to ``t_open``.
        Closed loop: ``clients`` callers drawing from ``pool_reqs``.
        ``on_open`` is called once when the window opens; returns the time
        the loop stopped."""
        i = 0
        queue = iter(pool_reqs or [])
        if clients:
            for c in range(clients):
                self.client_of[self.submit_req(next(queue), start)] = c
        opened = False
        while True:
            now = time.monotonic()
            if not opened and now >= t_open:
                opened = True
                if on_open is not None:
                    on_open()
            if now >= t_end:
                return now
            if schedule is not None:
                while i < len(schedule) and t_open + schedule[i].due <= now:
                    self.submit_req(schedule[i], t_open + schedule[i].due)
                    i += 1
            busy = [e for e in self.pool.engines if e.waiting or e.active]
            for eng in busy:
                for st in self.step(eng):
                    c = self.client_of.pop(st.request.rid, None)
                    if c is not None:
                        rid = self.submit_req(next(queue), st.finish_time)
                        self.client_of[rid] = c
            if busy:
                continue
            nxt = t_end
            if schedule is not None and i < len(schedule):
                nxt = min(nxt, t_open + schedule[i].due)
            if not opened:
                nxt = min(nxt, t_open)
            with self.span("wait_arrival"):
                time.sleep(max(nxt - time.monotonic(), 0.0))


def warm_up(driver: Driver, engines: List, seed: int) -> None:
    """Serve two requests of random tokens on every engine, so each step
    shape of each is compiled before the window: an engine prefills in
    power-of-two chunks, so prompts of the largest chunk ``c`` and of
    ``c - 1`` (every smaller size once) take each chunk size, and their
    decode steps the decode shape."""
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 99])
    now = time.monotonic()
    for eng in engines:
        top = max(eng._chunk_sizes)
        for n in (top, top - 1):
            driver.submit(rng.integers(1, driver.vocab, n).tolist(),
                          WARMUP_NEW, now, engine=eng)
    while any(e.waiting or e.active for e in engines):
        for eng in engines:
            if eng.waiting or eng.active:
                driver.step(eng)
    driver.reqs.clear()
    driver.steps.clear()
    driver.states.clear()


def window_record(driver: Driver, t0: float, t1: float, a, slots: int,
                  peaks: Optional[dict], reduced=None,
                  trace_window: Optional[tuple] = None) -> dict:
    """What the per-layer readers (``bench/metrics``) read: the window,
    every request and step the driver recorded, the model's shapes, the
    chip's peaks and the reduced trace (``None`` when untraced)."""
    return {"t0": t0, "t1": t1, "seconds": t1 - t0, "reqs": driver.reqs,
            "steps": driver.steps, "arch": a, "page": driver.page,
            "slots": slots, "peaks": peaks, "trace": reduced,
            "trace_window": trace_window or (t0, t1)}


# --------------------------------------------------------------------------- #
# end-to-end metrics (host clock)
# --------------------------------------------------------------------------- #
def end_to_end(driver: Driver, t0: float, t1: float, setup_s: float
               ) -> Dict[str, float]:
    due_in = [r for r in driver.reqs.values() if t0 <= r.due < t1]
    ttft = [(min(r.first, t1) if r.first is not None else t1) - r.due
            for r in due_in]
    gaps, emitted = [], 0
    for r in driver.reqs.values():
        ts = r.times
        emitted += sum(t0 <= t < t1 for t in ts)
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if t0 <= b < t1)
    out = {"setup_s": setup_s,
           "output_tokens_per_s": emitted / (t1 - t0)}
    if ttft:
        out["ttft_p90_s"] = stats.nearest_rank(ttft, 0.90)
    if gaps:
        out["itl_p95_ms"] = stats.nearest_rank(gaps, 0.95) * 1e3
        out["itl_mean_ms"] = sum(gaps) / len(gaps) * 1e3
    return out


# --------------------------------------------------------------------------- #
# correctness
# --------------------------------------------------------------------------- #
def sample(states: List, seed: int, tokens: int, max_requests: int) -> List:
    """Finished requests to check: the one with the longest sequence, then
    others in the seed's order until ``tokens`` served tokens or
    ``max_requests`` requests."""
    if not states:
        return []
    states = sorted(states, key=lambda s: s.request.rid)
    longest = max(states, key=lambda s: (len(s.request.prompt)
                                         + len(s.generated)))
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 5])
    rest = [states[i] for i in rng.permutation(len(states))
            if states[i] is not longest]
    out, n = [longest], len(longest.generated)
    for st in rest:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(st)
        n += len(st.generated)
    return out


def bucket(n: int) -> int:
    from bench.reference.model import ROW_BLOCK
    s = ROW_BLOCK
    while s < n:
        s *= 2
    return s


def served_sequences(states: List) -> List[tuple]:
    """(prompt, served tokens) of each sampled request, as the engine
    served them (a prompt it truncated is checked as truncated)."""
    return [(list(st.request.prompt), list(st.generated)) for st in states]


def reference_gaps(w, a, seqs: List[tuple], mode: str = "f32",
                   targets_from: Optional[str] = None) -> tuple:
    """For each (prompt, served) sequence, the float32 reference's gap at
    every served token: its best logit less the logit of the served token
    (or, with ``targets_from``, of the token that the ``targets_from``
    precision puts first there); and its router margin there (+inf for a
    dense model).  Returns the two lists."""
    import jax
    import jax.numpy as jnp

    from bench.reference.model import scores
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        for prompt, served in seqs:
            seq = prompt + served
            S = bucket(len(seq))
            tok = np.zeros(S, np.int32)
            tok[:len(seq)] = seq
            tgt = np.zeros(S, np.int32)
            tgt[:len(seq) - 1] = seq[1:]
            lo, hi = len(prompt) - 1, len(seq) - 1
            if targets_from is not None:
                _, _, arg, _ = scores(w, jnp.asarray(tok), jnp.asarray(tgt),
                                      a=a, mode=targets_from)
                tgt[lo:hi] = np.asarray(arg)[lo:hi]
            best, got, _, margin = scores(w, jnp.asarray(tok),
                                          jnp.asarray(tgt), a=a, mode=mode)
            out.append(np.asarray(best - got)[lo:hi])
            margins.append(np.full(hi - lo, np.inf, np.float32)
                           if margin is None else np.asarray(margin)[lo:hi])
    return out, margins


def free_device_state() -> None:
    """Drop every array the process holds on its devices."""
    import jax
    gc.collect()
    for x in jax.live_arrays():
        x.delete()
    gc.collect()


def ties(margins: Optional[List[np.ndarray]], limits: dict
         ) -> Optional[np.ndarray]:
    """Which served positions are router ties: the reference's router margin
    there is below the limits' ``router_tie_margin``.  None without it."""
    if "router_tie_margin" not in limits:
        return None
    m = np.concatenate(margins) if margins else np.zeros(0)
    return m < float(limits["router_tie_margin"])


def judge(gaps: List[np.ndarray], limits: dict,
          margins: Optional[List[np.ndarray]] = None) -> Dict[str, dict]:
    """The numbers compared, each beside its limit: how many served tokens
    were checked (at least ``min_tokens_checked``) and the widest gap
    (at most ``max_logit_gap``).  Where the limits give a
    ``router_tie_margin``, the tokens at router ties are left out of both,
    and where they give a ``max_router_tie_share``, the share of ties among
    the served tokens is held to it (``router_tie_share``)."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    tie = ties(margins, limits)
    if tie is not None:
        g = g[~tie]
    served = int(len(g))
    widest = float(g.max()) if served else math.inf
    out = {"served_tokens_checked": {
               "value": served, "limit": int(limits["min_tokens_checked"]),
               "ok": served >= int(limits["min_tokens_checked"])},
           "max_logit_gap": {
               "value": widest, "limit": float(limits["max_logit_gap"]),
               "ok": widest <= float(limits["max_logit_gap"])}}
    if tie is not None and "max_router_tie_share" in limits:
        share = float(tie.mean()) if len(tie) else 1.0
        out["router_tie_share"] = {
            "value": share, "limit": float(limits["max_router_tie_share"]),
            "ok": share <= float(limits["max_router_tie_share"])}
    return out


# --------------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------------- #
def start_trace(trace_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def reduce_trace(trace_dir: str):
    from bench import trace_reduce
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not files:
        raise RuntimeError(f"profiler wrote no .xplane.pb under {trace_dir}")
    return trace_reduce.reduce_file(files[0])


def gap_readings(gaps: List[np.ndarray], margins: List[np.ndarray],
                 limits: dict) -> dict:
    """The gaps' spread and the five widest, each with its router margin
    (None for a dense model); with a ``router_tie_margin`` in ``limits``, also
    the ties and the widest gap at the other (decisive) positions."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not len(g):
        return {"tokens": 0}
    m = np.concatenate(margins)
    out = {"tokens": int(len(g)), "max": float(g.max()),
           "p99": float(np.quantile(g, 0.99)), "mean": float(g.mean()),
           "nonzero": int((g > 0).sum()),
           "widest": [[float(g[i]), float(m[i]) if np.isfinite(m[i])
                       else None] for i in np.argsort(-g)[:5]]}
    tie = ties(margins, limits)
    if tie is not None:
        out["ties"] = int(tie.sum())
        out["decisive_max"] = (float(g[~tie].max()) if (~tie).any()
                               else None)
    return out


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        process_start: float, require_tpu: bool = True,
        root: Path = spec.ROOT, control: bool = False) -> dict:
    """One run of ``cell``; returns the result object that ``run.py``
    prints as its last line.  ``control`` also puts the lower-precision
    control in the program's place on the same sequences and judges it by
    the cell's own limits: ``control_correct`` and the readings of both
    (``bench/control.py``; the benchmark's runs never make them)."""
    import jax
    devs = devices_for(cell.chips, require_tpu)
    peaks = load_peak(devs[0].device_kind, root) if require_tpu else None
    mix = cell.traffic
    with CompileClock() as setup_clock:
        cfg, a, backend = build(cell, seed)
        pool = backend.pool
        engines = list(pool.engines)
        eng = engines[0]
        page = eng.page_size
        driver = Driver(pool, cfg.name, mix, seed, cfg.vocab_size, page)
        warm_up(driver, engines, seed)
    log(f"[setup] {cfg.name} {len(engines)} x {type(eng).__name__} "
        f"slots={eng.n_slots} max_seq_len={eng.max_seq_len} "
        f"pages={eng.page_pool.n_pages} fused_kernel={eng.use_paged_kernel} "
        f"interpret={eng.interpret}; {setup_clock}")

    preroll = float(mix["preroll_s"])
    schedule = pool_reqs = None
    clients = 0
    if mix["loop"] == "open":
        schedule = traffic.open_schedule(mix, seconds)
    else:
        clients = int(mix["clients"])
        pool_reqs = traffic.closed_pool(
            mix, clients + int(math.ceil((preroll + seconds) * 64)))

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        start_trace(trace_dir)
    t_start = time.monotonic()
    t0 = t_start + preroll
    t1 = t0 + seconds
    opened = {}

    def on_open():
        # the annotation's span starts when it is made, so it is made here
        opened["t"] = time.monotonic()
        opened["span"] = jax.profiler.TraceAnnotation("window")
        opened["span"].__enter__()

    with CompileClock() as window_clock:
        t_stop = driver.run(t_start, t0, t1, schedule=schedule,
                            clients=clients, pool_reqs=pool_reqs,
                            on_open=on_open)
    if "span" in opened:
        opened["span"].__exit__(None, None, None)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        if devs[0].platform == "tpu":      # a CPU trace has no device plane
            reduced = reduce_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = t0 - process_start
    e2e = end_to_end(driver, t0, t1, setup_s)
    mem = peak_bytes(devs[:cell.chips])
    log(f"[window] {seconds}s from t0+{opened.get('t', t0) - t0:.3f}s: "
        f"{len(driver.steps)} steps; {window_clock}")
    if window_clock.compiles:
        log(f"[window] WARNING: {window_clock.compiles} compiles inside the "
            f"measured window")

    record = window_record(driver, t0, t1, a, eng.n_slots, peaks, reduced,
                           (opened.get("t", t0), t_stop))
    attempted = sum(t0 <= r.due < t1 for r in driver.reqs.values())
    failed = sum(r.refused for r in driver.reqs.values() if t0 <= r.due < t1)

    # ---- correctness, after the window, with the program's state freed --
    checks: Dict[str, dict] = {}
    kernel_ok = all(e.use_paged_kernel and not e.interpret for e in engines)
    if require_tpu:
        checks["fused_decode_kernel"] = {"value": int(kernel_ok), "limit": 1,
                                         "ok": bool(kernel_ok)}
    done = [driver.states[r.rid] for r in driver.reqs.values()
            if r.finish is not None and r.rid in driver.states]
    lim = cell.limits
    picked = sample(done, seed, int(lim["sample_tokens"]),
                    int(lim["sample_requests"]))
    seqs = served_sequences(picked)
    del driver.states, done, picked, eng, engines, pool, backend
    free_device_state()
    from bench import weights
    t_ref = time.monotonic()
    w = weights.make(a, seed, chip_devices(cell.chips))
    gaps, margins = reference_gaps(w, a, seqs)
    readings = {"program": gap_readings(gaps, margins, lim)}
    control_checks = None
    if control:
        ctrl_gaps, _ = reference_gaps(w, a, seqs, targets_from="fp8")
        readings["control"] = gap_readings(ctrl_gaps, margins, lim)
        control_checks = judge(ctrl_gaps, lim, margins)
    del w
    free_device_state()
    checks.update(judge(gaps, lim, margins))
    log(f"[check] program gaps {json.dumps(readings['program'])}")
    log(f"[check] reference over {len(seqs)} requests "
        f"({sum(len(s[1]) for s in seqs)} served tokens) in "
        f"{time.monotonic() - t_ref:.1f}s")
    correct = all(c["ok"] for c in checks.values())

    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed)}
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m.name, root)(record)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
    else:
        metrics = {m.name: {"value": float(e2e[m.name]), "unit": m.unit}
                   for m in cell.end_to_end if m.name in e2e}
    result["metrics"] = metrics
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": mem}
    if reduced is not None:
        from bench.trace_reduce import short_name
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        per_chip = reduced.devices
        result["breakdown"] = {
            "device_ops": [[short_name(k), v / per_chip] for k, v in sorted(
                reduced.op_self_s.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[k, v] for k, v in sorted(
                reduced.idle_by_span.items(), key=lambda kv: -kv[1])[:10]],
        }
    result["device"] = dev
    for k, v in e2e.items():
        log(f"[e2e] {k}={v}")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} limit {c['limit']} "
            f"{'ok' if c['ok'] else 'FAIL'}")
    if control:
        for name, c in control_checks.items():
            log(f"control check {name}: {c['value']} limit {c['limit']} "
                f"{'ok' if c['ok'] else 'FAIL'}")
        result["control_correct"] = all(c["ok"]
                                        for c in control_checks.values())
        result["readings"] = readings
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def load_peak(kind: str, root: Path = spec.ROOT) -> dict:
    with open(root / "bench" / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise spec.SpecError(f"device kind {kind!r} has no row in "
                             f"bench/peaks.json")
    return table[kind]
