#!/usr/bin/env python3
"""Serve a model at its published widths on TPU, through the normal path.

    python3 chip_smoke.py              # one chip: qwen2-1.5b, full width
    python3 chip_smoke.py --four-chip  # a four-chip host: TP and EP replicas

One chip (the default): qwen2-1.5b is built at its published widths (no
``reduced()``, random weights from ``--seed``) as one ``TPU-v5e`` replica
group of 8 slots, ``max_seq_len`` 1024, page size 16, and serves 8 seeded
requests (prompts of 128-512 tokens, 32 new tokens each) through
``repro.launch.serve.serve``: ``JaxBackend.apply_plan`` → ``EnginePool``
submit → ``run_until_drained`` → the paged ``Engine``, whose decode runs the
compiled fused paged flash-decode kernel.  The requests come in two waves;
the second shares prompt prefixes with the first, so the prefix index maps
pages.  Checks: every request finishes with its token count; the engine
runs the compiled kernel (not the interpreter); the kernel agrees with
``paged_reference`` at the served shapes; the engine's fused decode step
agrees with its own gather path.

Four chips (``--four-chip``, this phase only): two tp=2 qwen2-1.5b replicas,
each a ``ShardedEngine`` on its own carved submesh with the fused
``shard_map`` decode, compared with a one-chip ``Engine`` on the same
prompts; and one tp=4 mixtral-8x7b replica at published widths cut to 2 of
32 layers, whose expert-parallel ``moe_gmm`` and fused decode are compared
with the GSPMD dense-mix gather path on the same mesh.

The script never chooses a platform: it exits non-zero, printing no result,
when JAX finds no TPU, when the repository's ``src/`` is missing, or when
any check or phase raises.  The one-chip phase keeps its compile cache where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``; the
four-chip phase turns the persistent cache off (sharded executables loaded
from it halt the chip, see ``main``).  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

SLOTS = 8
MAX_SEQ = 1024
PAGE = 16
NEW_TOKENS = 32
# wave 1 prompt lengths; wave 2 reuses the first SHARED[i] tokens of wave-1
# prompt i and ends at WAVE2[i] tokens (all multiples of the page size, so
# the shared prefix is whole pages)
WAVE1 = (128, 208, 320, 496)
SHARED = (128, 192, 256, 448)
WAVE2 = (192, 240, 400, 512)

# bf16 tolerances, fixed before the first chip run.  Kernel vs the f32
# reference: elementwise |out - ref| <= ATOL + RTOL * |ref| (the repo's bf16
# kernel-test tolerance).  Decode logits of two paths through a bf16 model:
# max |a - b| <= LOGIT_TOL * std(b); random-weight logits have std ~0.6 and
# a wrong page, head or expert moves them by about one std.
KERNEL_ATOL = KERNEL_RTOL = 2e-2
LOGIT_TOL = 0.25


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling inside the block,
    and how many backend compiles it ran (persistent-cache hits count as
    hits, not compiles)."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __enter__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, secs, **_):
            if event in self.EVENTS:
                self.seconds += secs
                self.compiles += event == self.EVENTS[-1]

        def on_event(event, **_):
            self.cache_hits += event == "/jax/compilation_cache/cache_hits"
        self._listeners = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listeners[0])
        jax.monitoring.unregister_event_listener(self._listeners[1])
        return False

    def __str__(self):
        return (f"compile_s={self.seconds:.2f} compiles={self.compiles} "
                f"cache_hits={self.cache_hits}")


def _requests(vocab: int, seed: int):
    """Two waves of 4 seeded requests; wave 2 shares page-aligned prompt
    prefixes with wave 1."""
    from repro.serving.engine import Request
    rng = np.random.default_rng(seed)
    wave1 = [rng.integers(1, vocab, n).tolist() for n in WAVE1]
    wave2 = []
    for src, share, n in zip(wave1, SHARED, WAVE2):
        tail = rng.integers(1, vocab, n - share).tolist()
        if share < len(src) and tail[0] == src[share]:
            tail[0] = tail[0] % (vocab - 1) + 1      # diverge right at the cut
        wave2.append(src[:share] + tail)

    def make(prompts, rid0):
        return [Request(rid=rid0 + i, prompt=p, max_new_tokens=NEW_TOKENS)
                for i, p in enumerate(prompts)]
    return make(wave1, 0), make(wave2, len(wave1))


def _check_finished(done, requests) -> int:
    want = {r.rid: r.max_new_tokens for r in requests}
    got = {d.request.rid: len(d.generated) for d in done}
    check(got == want, f"finished token counts {got} != requested {want}")
    return sum(got.values())


def check_fused_compiled(eng) -> None:
    """The engine decodes with the fused paged kernel, compiled."""
    check(eng.paged, f"{type(eng).__name__} is not paged")
    check(eng.use_paged_kernel, "engine decodes without the fused kernel")
    check(not eng.interpret, "engine runs Pallas kernels in interpret mode")


def _weights(backend, eng) -> str:
    """The served weights: bytes a step reads, and the leaves rounded to the
    compute dtype by the backend and then by the engine (0: already done)."""
    return (f"param_bytes={eng.param_bytes} "
            f"params_cast={backend.params_cast}+{eng.params_cast}")


def _peak_bytes(devices) -> str:
    parts = []
    for d in devices:
        stats = d.memory_stats() or {}
        parts.append(f"{d.id}:{stats.get('peak_bytes_in_use', 'n/a')}")
    return " ".join(parts)


def decode_logits(eng, prompt, *, use_kernel: bool, flags_scope) -> np.ndarray:
    """Logits of one decode step after prefilling ``prompt`` into slot 0 of
    a fresh pool laid out (and sharded) like ``eng``'s — through
    ``lm.paged_step`` with ``eng``'s parameters, under ``flags_scope``.
    The decode step is fed ``prompt[0]``, so two paths see the same input
    whatever their prefill argmax."""
    import jax
    import jax.numpy as jnp

    from repro.models import flags, lm
    cfg, page, interpret = eng.cfg, eng.page_size, eng.interpret
    cache = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype, device=a.sharding), eng.cache)
    n = len(prompt)
    ptab = np.zeros((eng.n_slots, -(-eng.max_seq_len // page)), np.int32)
    blocks = n // page + 1
    ptab[0, :blocks] = np.arange(1, blocks + 1)
    act = np.zeros((eng.n_slots,), bool)
    act[0] = True

    def step(p, c, t, pos, pt, a):
        return lm.paged_step(p, cfg, c, t, pos, pt, a, page_size=page,
                             use_kernel=use_kernel, interpret=interpret)
    step = jax.jit(step)
    tok = np.zeros((eng.n_slots, n), np.int32)
    pos = np.zeros((eng.n_slots, n), np.int32)
    tok[0], pos[0] = prompt, np.arange(n)
    tok1 = np.zeros((eng.n_slots, 1), np.int32)
    pos1 = np.zeros((eng.n_slots, 1), np.int32)
    tok1[0, 0], pos1[0, 0] = prompt[0], n
    with flags.scoped(**flags_scope):
        _, cache = step(eng.params, cache, tok, pos, ptab, act)
        logits, _ = step(eng.params, cache, tok1, pos1, ptab, act)
    return np.asarray(jax.device_get(logits[0, 0]), np.float32)


def compare_logits(label: str, got: np.ndarray, ref: np.ndarray) -> None:
    check(bool(np.isfinite(got).all() and np.isfinite(ref).all()),
          f"{label}: non-finite logits")
    diff = float(np.max(np.abs(got - ref)))
    std = float(np.std(ref))
    same = int(np.argmax(got)) == int(np.argmax(ref))
    print(f"[logits] {label}: max|diff|={diff:.5f} ref_std={std:.5f} "
          f"tol={LOGIT_TOL * std:.5f} argmax_agree={same}")
    check(diff <= LOGIT_TOL * std,
          f"{label}: logits differ by {diff:.5f} > {LOGIT_TOL} x std {std:.5f}")


def check_kernel_vs_reference(eng, seed: int) -> None:
    """The compiled paged kernel against the jnp oracle at the engine's
    served shapes (its pool size, slots, page table width)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_decode import ops as fd_ops
    cfg = eng.cfg
    n_pages = eng.page_pool.n_pages
    pps = -(-eng.max_seq_len // eng.page_size)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 5)
    q = jax.random.normal(ks[0], (eng.n_slots, cfg.n_heads, cfg.d_head),
                          jnp.bfloat16)
    pool = (n_pages, cfg.n_kv_heads, eng.page_size, cfg.d_head)
    kp = jax.random.normal(ks[1], pool, jnp.bfloat16)
    vp = jax.random.normal(ks[2], pool, jnp.bfloat16)
    ptab = jax.random.randint(ks[3], (eng.n_slots, pps), 1, n_pages)
    lens = jax.random.randint(ks[4], (eng.n_slots,), 1, MAX_SEQ + 1)
    lens = lens.at[0].set(MAX_SEQ).at[1].set(1)
    out = fd_ops.paged_flash_decode(q, kp, vp, ptab, lens,
                                    window=None, interpret=eng.interpret)
    f32 = jnp.float32
    ref = fd_ops.paged_reference(q.astype(f32), kp.astype(f32),
                                 vp.astype(f32), ptab, lens)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    err = np.abs(out - ref)
    bound = KERNEL_ATOL + KERNEL_RTOL * np.abs(ref)
    print(f"[kernel] paged_flash_decode vs paged_reference at q{q.shape} "
          f"pool{pool} ptab{tuple(ptab.shape)}: max|err|={err.max():.5f} "
          f"(bound {KERNEL_ATOL} + {KERNEL_RTOL}*|ref|)")
    check(bool((err <= bound).all()), "fused kernel disagrees with reference")


def one_chip_phase(seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.core.plan import Plan, ReplicaGroup
    from repro.launch.serve import serve
    from repro.models import lm
    from repro.serving.backend import JaxBackend

    cfg = get_config("qwen2-1.5b")
    print(f"[arch] {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
          f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} "
          f"d_head={cfg.d_head} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"params={cfg.param_count() / 1e9:.3f}B dtype={cfg.dtype}")
    t0 = time.monotonic()
    params = jax.block_until_ready(lm.init_params(cfg, jax.random.PRNGKey(seed)))
    print(f"[init] random weights from seed {seed} in "
          f"{time.monotonic() - t0:.2f}s")
    backend = JaxBackend(cfg, params, max_seq_len=MAX_SEQ, slots_cap=SLOTS,
                         max_replicas_per_group=1)
    del params                          # the backend holds its served copy
    plan = Plan((ReplicaGroup(cfg.name, "TPU-v5e", tp=1, batch=SLOTS,
                              count=1),))
    waves = _requests(cfg.vocab_size, seed)
    tokens = 0
    for i, wave in enumerate(waves, 1):
        with CompileClock() as clock:
            res = serve(backend, plan, wave)
        tokens += _check_finished(res.done, wave)
        eng = backend.pool.engines[0]
        if i == 1:
            check(len(res.report.built) == 1, f"plan built {res.report}")
            check_fused_compiled(eng)
            print(f"[engine] {type(eng).__name__} slots={eng.n_slots} "
                  f"max_seq_len={eng.max_seq_len} page={eng.page_size} "
                  f"pages={eng.page_pool.n_pages} "
                  f"fused_kernel={eng.use_paged_kernel} "
                  f"interpret={eng.interpret} {_weights(backend, eng)}")
        print(f"[serve] wave {i}: {len(res.done)} requests "
              f"(prompts {[len(r.prompt) for r in wave]}) "
              f"{res.tokens} tokens in {res.wall_s:.2f}s; {clock}")
    hit_pages = eng.prefix_index.tokens_matched // eng.page_size
    print(f"[prefix] hits={eng.prefix_index.hits} hit_pages={hit_pages}")
    check(hit_pages >= sum(SHARED) // PAGE,
          f"wave 2 mapped {hit_pages} prefix pages, expected "
          f">= {sum(SHARED) // PAGE}")
    print(f"[serve] all {2 * len(WAVE1)} requests finished, {tokens} tokens")

    check_kernel_vs_reference(eng, seed)
    prompt = waves[0][0].prompt
    with CompileClock() as clock:
        fused = decode_logits(eng, prompt, use_kernel=True, flags_scope={})
        gather = decode_logits(eng, prompt, use_kernel=False, flags_scope={})
    compare_logits("one chip: fused kernel vs gather path", fused, gather)
    print(f"[probe] {clock}")
    print(f"[memory] peak_bytes_in_use {_peak_bytes(jax.devices()[:1])}")


def _device_ids(eng):
    return sorted(d.id for d in eng.mesh.devices.flatten())


def _serve_tp_replicas(cfg, params, requests):
    """Serve ``requests`` on two tp=2 replicas through the pool; returns
    the generated tokens per rid and each replica's decode logits on the
    first prompt.  The replicas die with this frame, so their shards leave
    device 0 before the one-chip reference runs there."""
    import jax

    from repro.core.plan import Plan, ReplicaGroup
    from repro.launch.serve import serve
    from repro.serving.backend import JaxBackend
    from repro.serving.sharded import ShardedEngine

    backend = JaxBackend(cfg, params, max_seq_len=MAX_SEQ, slots_cap=SLOTS,
                         max_replicas_per_group=2)
    plan = Plan((ReplicaGroup(cfg.name, "TPU-v5e", tp=2, batch=SLOTS,
                              count=2),))
    with CompileClock() as clock:
        res = serve(backend, plan, requests)
    _check_finished(res.done, requests)
    engines = backend.pool.engines
    check(len(engines) == 2, f"{len(engines)} replicas built, want 2")
    logits = {}
    for eng in engines:
        check(isinstance(eng, ShardedEngine),
              f"replica is a {type(eng).__name__}, not a ShardedEngine")
        check(eng.tp == 2, f"replica tp={eng.tp}")
        check(eng.paged_kernel_fused and "paged_shard" in eng.trace_flags,
              "replica does not run the fused shard_map decode")
        check_fused_compiled(eng)
        ids = tuple(_device_ids(eng))
        print(f"[tp] replica {type(eng).__name__} tp={eng.tp} "
              f"devices={list(ids)} fused_shard_map={eng.paged_kernel_fused} "
              f"{_weights(backend, eng)}")
        logits[ids] = decode_logits(eng, requests[0].prompt, use_kernel=True,
                                    flags_scope=eng.trace_flags)
    a, b = logits
    check(not set(a) & set(b), f"submeshes overlap: {list(logits)}")
    print(f"[tp] served {len(res.done)} requests, {res.tokens} tokens in "
          f"{res.wall_s:.2f}s; {clock}")
    print(f"[memory] peak_bytes_in_use {_peak_bytes(jax.devices()[:4])}")
    return {d.request.rid: d.generated for d in res.done}, logits


def four_chip_tp(seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.models import lm
    from repro.serving.engine import Engine

    cfg = get_config("qwen2-1.5b")
    print(f"[tp] {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
          f"vocab={cfg.vocab_size}: 2 replicas x tp=2")
    params = jax.block_until_ready(lm.init_params(cfg, jax.random.PRNGKey(seed)))
    requests = [r for wave in _requests(cfg.vocab_size, seed) for r in wave]
    got_tokens, got_logits = _serve_tp_replicas(cfg, params, requests)
    gc.collect()

    ref = Engine(cfg, params, n_slots=SLOTS, max_seq_len=MAX_SEQ,
                 page_size=PAGE)
    check_fused_compiled(ref)
    for r in requests:
        ref.submit(dataclasses.replace(r, arrival_time=0.0))
    ref_tokens = {d.request.rid: d.generated for d in ref.run_until_drained()}
    lead = []
    for rid, want in sorted(ref_tokens.items()):
        got = got_tokens[rid]
        k = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
                 len(want))
        lead.append(k)
    same = sum(k == NEW_TOKENS for k in lead)
    print(f"[tp] greedy-token agreement with the one-chip Engine: "
          f"{same}/{len(lead)} requests identical; agreeing leading tokens "
          f"per request {lead}")
    want = decode_logits(ref, requests[0].prompt, use_kernel=True,
                         flags_scope={})
    for ids, got in got_logits.items():
        compare_logits(f"tp=2 replica on {list(ids)} vs one-chip Engine",
                       got, want)
    print(f"[memory] peak_bytes_in_use {_peak_bytes(jax.devices()[:4])}")


def four_chip_ep(seed: int) -> None:
    import jax
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.core.plan import Plan, ReplicaGroup
    from repro.distributed import sharding
    from repro.launch.serve import serve
    from repro.models import lm
    from repro.serving.backend import JaxBackend
    from repro.serving.sharded import ShardedEngine

    full = get_config("mixtral-8x7b")
    cfg = dataclasses.replace(full, n_layers=2)
    print(f"[ep] {cfg.name} d_model={cfg.d_model} d_ff={cfg.d_ff} "
          f"experts={cfg.n_experts} top_k={cfg.top_k} heads={cfg.n_heads} "
          f"kv_heads={cfg.n_kv_heads} vocab={cfg.vocab_size} "
          f"layers={cfg.n_layers} of {full.n_layers}: 1 replica x tp=4")
    devices = sorted(jax.devices(), key=lambda d: d.id)[:4]
    mesh = Mesh(np.array(devices).reshape(1, 4), ("data", "model"))
    pol = dataclasses.replace(sharding.make_policy(mesh, cfg), fsdp_axis=None)
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(lambda k: lm.init_params(cfg, k), key)
    specs = sharding.sharding_decision(cfg, pol, shapes).param_specs
    # made in place on the mesh: the f32 weights do not fit one chip
    params = jax.jit(lambda k: lm.init_params(cfg, k),
                     out_shardings=sharding._ns(mesh, specs))(key)
    backend = JaxBackend(cfg, params, max_seq_len=MAX_SEQ, slots_cap=SLOTS,
                         max_replicas_per_group=1)
    plan = Plan((ReplicaGroup(cfg.name, "TPU-v5e", tp=4, batch=SLOTS,
                              count=1),))
    wave, _ = _requests(cfg.vocab_size, seed)
    with CompileClock() as clock:
        res = serve(backend, plan, wave)
    _check_finished(res.done, wave)
    (eng,) = backend.pool.engines
    check(isinstance(eng, ShardedEngine),
          f"replica is a {type(eng).__name__}, not a ShardedEngine")
    check(eng.tp == 4 and eng.sharding_policy.ep, "replica is not tp=4 EP")
    check("ep_shard" in eng.trace_flags and "paged_shard" in eng.trace_flags,
          f"replica trace flags {sorted(eng.trace_flags)}")
    check_fused_compiled(eng)
    check(_device_ids(eng) == [d.id for d in devices],
          f"replica devices {_device_ids(eng)}")
    print(f"[ep] replica {type(eng).__name__} tp={eng.tp} ep=True "
          f"devices={_device_ids(eng)} flags={sorted(eng.trace_flags)} "
          f"{_weights(backend, eng)}")
    print(f"[ep] served {len(res.done)} requests, {res.tokens} tokens in "
          f"{res.wall_s:.2f}s; {clock}")
    prompt = wave[0].prompt
    got = decode_logits(eng, prompt, use_kernel=True,
                        flags_scope=eng.trace_flags)
    want = decode_logits(eng, prompt, use_kernel=False, flags_scope={})
    compare_logits("EP moe_gmm + fused decode vs GSPMD dense mix + gather",
                   got, want)
    print(f"[memory] peak_bytes_in_use {_peak_bytes(devices)}")


def four_chip_phase(seed: int) -> None:
    """TP then EP; a failure in one is reported and the other still runs
    (one four-chip run shows both), then the phase fails."""
    failed = []
    for name, part in (("tp", four_chip_tp), ("ep", four_chip_ep)):
        try:
            part(seed)
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            traceback.print_exc()
            failed.append(f"{name}: {e!r}")
        gc.collect()                  # free one model's weights before the next
    check(not failed, "four-chip phase failed: " + "; ".join(failed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip TP and EP phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    need = 4 if args.four_chip else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    if args.four_chip:
        # with jax 0.9.0 / libtpu 0.0.34, the tp=2 replicas' first prefill
        # halts the TPU ("program continuator has halted unexpectedly")
        # when its executables come from the persistent cache, and passes
        # when they are compiled in the run
        jax.config.update("jax_enable_compilation_cache", False)
        cache = "off"
    else:
        from repro.launch.compile_cache import use_compile_cache
        cache = use_compile_cache()
    print(f"[device] {devices[0].device_kind} x{len(devices)} "
          f"jax={jax.__version__} compile_cache={cache}")
    t0 = time.monotonic()
    (four_chip_phase if args.four_chip else one_chip_phase)(args.seed)
    print(f"[done] {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
