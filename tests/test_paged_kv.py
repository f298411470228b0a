"""Paged KV pool + cross-request prefix reuse: host-side pool/index units,
paged-vs-contiguous engine parity across cache families, prefix-hit token
identity, eviction under page pressure, page-granular slot migration (both
directions across the paged/contiguous wire format), and the evolvable
kv_cache policy domain up through a guarded canary rollback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.mutation import _CATEGORICAL, _NUMERIC_STEPS, _enable_domain_for
from repro.core.plan import ClusterState, HARDWARE, QWEN25_FAMILY, Workload
from repro.core.policy import DOMAINS, render_policy, seed_policies
from repro.models import lm
from repro.serving import kvcache
from repro.serving.engine import Engine, Request
from repro.serving.shadow import BAD_KV_SOURCE, ShadowBackend
from repro.traces.workload import (multi_turn_requests,
                                   shared_prefix_requests)

KEY = jax.random.PRNGKey(0)

_ZOO = {}


def _zoo(arch):
    if arch not in _ZOO:
        cfg = get_config(arch).reduced()
        _ZOO[arch] = (cfg, lm.init_params(cfg, KEY))
    return _ZOO[arch]


# --------------------------------------------------------------------------- #
# host structures: page pool + prefix index
# --------------------------------------------------------------------------- #
def test_page_pool_refcount_and_exhaustion():
    pool = kvcache.PagePool(4)            # pages 1..3 allocatable, 0 = trash
    a, b, c = pool.alloc(), pool.alloc(), pool.alloc()
    assert sorted([a, b, c]) == [1, 2, 3]
    assert pool.alloc() is None           # exhausted, caller must evict
    pool.ref(b)
    assert not pool.unref(b)              # still shared
    assert pool.unref(b)                  # last share frees
    assert pool.alloc() == b              # freed page is allocatable again
    with pytest.raises(ValueError):
        pool.unref(b + 10)                # never-allocated page
    pool.ref(kvcache.TRASH_PAGE)          # trash page: always a no-op
    assert not pool.unref(kvcache.TRASH_PAGE)


def test_prefix_index_match_caps_below_full_prompt():
    idx = kvcache.PrefixIndex(page_size=4)
    prompt = list(range(1, 13))           # 12 tokens = 3 full pages
    idx.insert(prompt, [5, 6, 7], now=0.0)
    pages, matched = idx.match(prompt, now=1.0)
    # cap at (len-1)//page: the final prompt token must still be prefilled
    assert pages == [5, 6] and matched == 8
    assert idx.hits == 1 and idx.tokens_matched == 8
    # a diverging second block stops the walk after one page
    pages2, matched2 = idx.match(prompt[:4] + [99] * 8, now=2.0)
    assert pages2 == [5] and matched2 == 4
    _, m3 = idx.match([99, 98, 97, 96, 95], now=3.0)
    assert m3 == 0 and idx.misses == 1


def test_prefix_index_insert_returns_only_new_nodes():
    idx = kvcache.PrefixIndex(page_size=4)
    first = idx.insert(list(range(8)), [3, 4], now=0.0)
    assert [n.page for n in first] == [3, 4]
    # shared first block: only the diverging tail is new (its canonical
    # page stays 3 — the caller refs exactly the returned nodes' pages)
    second = idx.insert(list(range(4)) + [50, 51, 52, 53], [9, 10], now=1.0)
    assert [n.page for n in second] == [10]
    assert idx.nodes == 3


def test_prefix_index_evicts_leaves_only():
    idx = kvcache.PrefixIndex(page_size=2)
    idx.insert([1, 2, 3, 4], [5, 6], now=0.0)
    [root] = [n for lvl in [idx.root] for n in lvl.values()]
    with pytest.raises(ValueError):
        idx.remove(root)                  # interior hole would break chains
    [leaf] = idx.leaves()
    assert idx.remove(leaf) == 6
    assert idx.leaves()[0] is root        # parent became the new leaf
    assert idx.remove(root) == 5 and idx.nodes == 0


# --------------------------------------------------------------------------- #
# paged flash-decode kernel vs reference (dense / GQA / sliding window)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("h,hkv,window", [(4, 4, None), (4, 2, None),
                                          (4, 2, 16)])
def test_paged_flash_decode_kernel_matches_ref(h, hkv, window):
    from repro.kernels.flash_decode.kernel import paged_flash_decode_kernel
    from repro.kernels.flash_decode.ref import paged_flash_decode_ref
    B, D, page, n_pages, pps = 3, 16, 8, 17, 6
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(k1, (B, h, D), jnp.float32)
    kp = jax.random.normal(k2, (n_pages, hkv, page, D), jnp.float32)
    vp = jax.random.normal(k3, (n_pages, hkv, page, D), jnp.float32)
    ptab = jax.random.randint(k4, (B, pps), 1, n_pages).astype(jnp.int32)
    kv_len = jnp.array([5, 23, 48], jnp.int32)
    out = paged_flash_decode_kernel(q, kp, vp, ptab, kv_len, window=window,
                                    interpret=True)
    ref = paged_flash_decode_ref(q, kp, vp, ptab, kv_len, window=window)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5


# --------------------------------------------------------------------------- #
# engine parity: paged pool ≡ contiguous per-slot cache (greedy-exact)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("arch", [
    "qwen2-1.5b",        # dense GQA
    "mixtral-8x7b",      # pure-SWA MoE (window mask, no ring rotation)
    "minicpm3-4b",       # MLA compressed-latent pool
])
def test_paged_engine_matches_contiguous(arch):
    cfg, params = _zoo(arch)
    prompts = [[1 + (3 * i + r) % 17 for i in range(23 - r)] for r in range(5)]

    def run(paged):
        eng = Engine(cfg, params, n_slots=3, max_seq_len=48, paged=paged,
                     page_size=4)
        for r, p in enumerate(prompts):
            eng.submit(Request(rid=r, prompt=list(p), max_new_tokens=6))
        return {d.request.rid: d.generated for d in eng.run_until_drained()}

    assert run(paged=False) == run(paged=True)


def test_pageable_gate_and_defaults():
    cfg, params = _zoo("qwen2-1.5b")
    assert Engine(cfg, params, n_slots=1, max_seq_len=32).paged
    for arch in ("mamba2-1.3b", "gemma2-9b"):   # SSM state / local-global mix
        c2, p2 = _zoo(arch)
        assert not lm.pageable(c2)
        assert not Engine(c2, p2, n_slots=1, max_seq_len=32).paged
        with pytest.raises(ValueError):
            Engine(c2, p2, n_slots=1, max_seq_len=32, paged=True)


def test_prefix_hit_same_tokens_fewer_prefill_dispatches():
    cfg, params = _zoo("qwen2-1.5b")
    shared = [1 + (5 * i) % 19 for i in range(20)]   # 5 full pages

    eng = Engine(cfg, params, n_slots=2, max_seq_len=48, page_size=4)
    eng.submit(Request(rid=0, prompt=shared + [30], max_new_tokens=4))
    eng.run_until_drained()
    eng.submit(Request(rid=1, prompt=shared + [31], max_new_tokens=4))
    hit = eng.run_until_drained()[-1]

    cold = Engine(cfg, params, n_slots=2, max_seq_len=48, page_size=4,
                  prefix_cache=False)
    cold.submit(Request(rid=1, prompt=shared + [31], max_new_tokens=4))
    miss = cold.run_until_drained()[0]

    assert hit.generated == miss.generated           # numerically identical
    assert hit.prefill_dispatches < miss.prefill_dispatches
    assert eng.prefix_hits == 1 and eng.prefix_tokens_saved == 20
    assert cold.prefix_hits == 0


def test_multi_turn_chain_reuses_growing_prefix():
    """Agentic shape: each turn's prompt extends the last — the retained
    prefix (prompt + generated) of turn k is matched by turn k+1."""
    cfg, params = _zoo("qwen2-1.5b")
    eng = Engine(cfg, params, n_slots=1, max_seq_len=64, page_size=4)
    [chain] = multi_turn_requests(1, 3, turn_len=12, seed=5)
    for t, prompt in enumerate(chain):
        eng.submit(Request(rid=t, prompt=list(prompt), max_new_tokens=2))
        eng.run_until_drained()
    assert eng.prefix_hits == 2                      # turns 2 and 3 hit
    assert eng.prefix_tokens_saved >= 2 * 8


def test_eviction_under_page_pressure_stays_correct():
    cfg, params = _zoo("qwen2-1.5b")
    pps = -(-48 // 4)
    eng = Engine(cfg, params, n_slots=1, max_seq_len=48, page_size=4,
                 n_pages=1 + 2 * pps)                # room for ~1 retained set
    reqs = shared_prefix_requests(6, prefix_pool=6, prefix_len=20,
                                  suffix_len=4, reuse_ratio=1.0, seed=2)
    outs = {}
    for rid, (_, prompt) in enumerate(reqs):
        eng.submit(Request(rid=rid, prompt=list(prompt), max_new_tokens=3))
        outs[rid] = eng.run_until_drained()[-1].generated
    assert eng.prefix_evictions > 0                  # pressure really hit
    cold = Engine(cfg, params, n_slots=1, max_seq_len=48, page_size=4,
                  prefix_cache=False)
    for rid, (_, prompt) in enumerate(reqs):
        cold.submit(Request(rid=rid, prompt=list(prompt), max_new_tokens=3))
        assert cold.run_until_drained()[-1].generated == outs[rid]


# --------------------------------------------------------------------------- #
# page-granular slot migration, including across cache layouts
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mixtral-8x7b",
                                  "minicpm3-4b"])
@pytest.mark.parametrize("src_paged,dst_paged", [(True, True), (True, False),
                                                 (False, True)])
def test_paged_migration_round_trip(arch, src_paged, dst_paged):
    cfg, params = _zoo(arch)
    prompt = [1 + (3 * i) % 17 for i in range(23)]
    ref = Engine(cfg, params, n_slots=2, max_seq_len=48, paged=False)
    ref.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=8))
    want = ref.run_until_drained()[0].generated

    src = Engine(cfg, params, n_slots=2, max_seq_len=48, paged=src_paged,
                 page_size=4)
    src.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=8))
    src.step(); src.step(); src.step()
    [export] = src.export_active()
    assert not src.active

    dst = Engine(cfg, params, n_slots=3, max_seq_len=48, paged=dst_paged,
                 page_size=4)
    dst.submit(Request(rid=7, prompt=[2, 3, 4], max_new_tokens=10))
    dst.step()                                       # occupy slot 0 first
    assert dst.install_active(export)
    assert export.state.slot != 0
    done = dst.run_until_drained()
    got = next(d for d in done if d.request.rid == 0).generated
    assert got == want


def test_paged_export_releases_pages_into_prefix_index():
    cfg, params = _zoo("qwen2-1.5b")
    eng = Engine(cfg, params, n_slots=1, max_seq_len=48, page_size=4)
    eng.submit(Request(rid=0, prompt=list(range(1, 18)), max_new_tokens=8))
    eng.step(); eng.step()
    used_before = eng.page_pool.used_pages
    assert used_before > 0
    [export] = eng.export_active()
    # slot pages were handed to the prefix index (full blocks) or freed —
    # none remain bound to the departed slot
    assert not eng._slot_pages
    assert eng.prefix_index.nodes > 0
    # a continuation of the same request now prefix-hits its own history
    eng.submit(export.request)
    eng.run_until_drained()
    assert eng.prefix_hits == 1


# --------------------------------------------------------------------------- #
# kv_cache policy domain: genome, hooks, engine behaviour, canary guard
# --------------------------------------------------------------------------- #
def test_kv_cache_domain_registered_and_mutable():
    assert DOMAINS["kv_cache"] == ("cache_prefix", "evict_priority")
    assert "kv_evict_kind" in _CATEGORICAL
    assert "kv_admit_min_pages" in _NUMERIC_STEPS
    assert "kv_pin_hits" in _NUMERIC_STEPS
    g = {"domains": ["placement"]}
    _enable_domain_for(g, "kv_evict_kind")
    assert "kv_cache" in g["domains"]     # touching a knob turns the domain on


def test_kv_seed_policies_compile_and_hook():
    seeds = seed_policies()
    for name in ("kv-lru", "kv-prefix-pin"):
        pol = seeds[name]
        pol.compile()
        assert pol.implements("kv_cache")
        kp = pol.kv_cache_policy()
        ctx = kvcache.KVCacheCtx(prefix_pages=4, prompt_len=17, hits=3,
                                 idle_s=2.5, pool_free=10, pool_total=40)
        assert isinstance(kp.cache_prefix(ctx), bool)
        assert isinstance(kp.evict_priority(ctx), float)
    # pin-hot: a block at/above the pin bar scores far below a cold one
    kp = seeds["kv-prefix-pin"].kv_cache_policy()
    hot = kvcache.KVCacheCtx(4, 0, hits=5, idle_s=9.0, pool_free=0,
                             pool_total=40)
    cold = kvcache.KVCacheCtx(4, 0, hits=0, idle_s=9.0, pool_free=0,
                              pool_total=40)
    assert kp.evict_priority(hot) < kp.evict_priority(cold)


def test_kv_admission_policy_gates_retention():
    cfg, params = _zoo("qwen2-1.5b")
    strict = render_policy({"domains": ["placement", "kv_cache"],
                            "kv_admit_min_pages": 8}, name="strict")
    strict.compile()
    eng = Engine(cfg, params, n_slots=1, max_seq_len=48, page_size=4,
                 kv_cache_policy=strict.kv_cache_policy())
    shared = list(range(1, 21))                      # 5 pages < the 8 floor
    for rid in range(2):
        eng.submit(Request(rid=rid, prompt=shared + [30 + rid],
                           max_new_tokens=3))
        eng.run_until_drained()
    assert eng.prefix_index.nodes == 0 and eng.prefix_hits == 0


def test_cache_thrash_policy_rolled_back_by_canary():
    """The planted kv_cache regression (never cache + evict hottest first)
    must be caught by the guarded canary and the caching incumbent's hooks
    restored — the §6.2 safety rail extended to the fourth domain."""
    from repro.core.evaluator import Evaluator
    from repro.core.policy import Policy
    from repro.core.runtime import (CanaryTicket, DataPlane, PolicyStage,
                                    SnapshotBuffer)
    from repro.core.simulator import Simulator
    from repro.traces.workload import TimestampObservation, Trace

    models = {m.name: m for m in QWEN25_FAMILY.values()}
    sim = Simulator(models, HARDWARE)
    ev = Evaluator(sim, models, HARDWARE, candidate_timeout_s=20.0)
    c = ClusterState((("H100-80G", 8),))
    # prefill-heavy single-model load: TTFT is dominated by prefill, which
    # is exactly what prefix caching discounts.  The prefill length DRIFTS
    # each interval, so every interval brings fresh shared templates whose
    # first occupant must be retained for the rest of the burst to hit —
    # a policy that never caches can't re-warm and regresses unmistakably
    obs = tuple(TimestampObservation(
        i, float(i),
        (Workload(QWEN25_FAMILY["7B"].name, 64, 512 + 128 * i, 256),), c)
        for i in range(6))
    tr = Trace("kv-canary", obs, (QWEN25_FAMILY["7B"].name,))

    backend = ShadowBackend(sim, seed=0, requests_per_model=6)
    stage = PolicyStage()
    dp = DataPlane(ev, seed_policies()["kv-lru"], stage, SnapshotBuffer(),
                   backend=backend)
    dp.step(tr.observations[0])
    dp.step(tr.observations[1])
    assert backend.pool.kv_cache_policy is not None  # incumbent hooks live
    saved_before = sum(e.prefix_tokens_saved for e in backend.pool.engines)
    assert saved_before > 0                          # caching actually works

    stage.publish(Policy(source=BAD_KV_SOURCE, name="thrash"),
                  ticket=CanaryTicket(intervals=2, max_regression=0.2,
                                      policy_name="thrash"))
    out = dp.step(tr.observations[2])
    assert out["canary"]["status"] == "running"
    out = dp.step(tr.observations[3])
    assert out["canary"]["status"] == "rolled_back"
    assert dp.rollbacks == 1 and dp.commits == 0
    # incumbent kv hooks restored, and the thrash source is quarantined
    assert backend.pool.kv_cache_policy is not None
    assert backend.pool.kv_cache_policy.name == "kv-lru"
    assert stage.quarantined(BAD_KV_SOURCE)


# --------------------------------------------------------------------------- #
# workload generators (satellite: shared-prefix synthesis)
# --------------------------------------------------------------------------- #
def test_shared_prefix_generator_is_deterministic_and_shaped():
    a = shared_prefix_requests(40, prefix_pool=2, prefix_len=32,
                               suffix_len=8, reuse_ratio=0.75, seed=9)
    b = shared_prefix_requests(40, prefix_pool=2, prefix_len=32,
                               suffix_len=8, reuse_ratio=0.75, seed=9)
    assert a == b
    reused = [t for t, _ in a if t >= 0]
    assert 0.5 <= len(reused) / len(a) <= 0.95
    tpl_of = {}
    for t, prompt in a:
        if t < 0:
            assert len(prompt) == 40
            continue
        assert len(prompt) == 40
        head = tuple(prompt[:32])
        assert tpl_of.setdefault(t, head) == head    # same template ⇒ same head
    assert len(tpl_of) == 2


# --------------------------------------------------------------------------- #
# head-slice kernel entry point (shared by shard_map body + single device)
# --------------------------------------------------------------------------- #
def test_head_slice_blocks_tile_the_full_kernel_output():
    from repro.kernels.flash_decode import ops
    B, H, Hkv, D, page, pps = 2, 8, 4, 16, 8, 4
    n_pages = 1 + B * pps
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(k1, (B, H, D), jnp.float32)
    kp = jax.random.normal(k2, (n_pages, Hkv, page, D), jnp.float32)
    vp = jax.random.normal(k3, (n_pages, Hkv, page, D), jnp.float32)
    ptab = jax.random.randint(k4, (B, pps), 1, n_pages).astype(jnp.int32)
    kv_len = jnp.array([9, 27], jnp.int32)
    full = ops.paged_flash_decode(q, kp, vp, ptab, kv_len)
    G = H // Hkv
    for tp in (2, 4):
        width = Hkv // tp
        parts = [ops.paged_flash_decode_head_slice(
                     q, kp[:, i * width:(i + 1) * width],
                     vp[:, i * width:(i + 1) * width],
                     ptab, kv_len, i * width, Hkv, interpret=True)
                 for i in range(tp)]
        assert all(p.shape == (B, G * width, D) for p in parts)
        tiled = jnp.concatenate(parts, axis=1)
        assert jnp.max(jnp.abs(tiled - full)) == 0.0   # same kernel, same math


def test_head_slice_rejects_indivisible_gqa_groups():
    from repro.kernels.flash_decode import ops
    q = jnp.zeros((1, 8, 16), jnp.float32)
    kp = vp = jnp.zeros((3, 3, 8, 16), jnp.float32)
    ptab = jnp.ones((1, 2), jnp.int32)
    kv_len = jnp.array([4], jnp.int32)
    with pytest.raises(ValueError, match="divisible"):
        ops.paged_flash_decode_head_slice(q, kp, vp, ptab, kv_len, 0, 3)


# --------------------------------------------------------------------------- #
# per-stage lockstep pools/tries (PipelinedEngine paged bookkeeping)
# --------------------------------------------------------------------------- #
def test_staged_page_pool_keeps_stage_pools_in_lockstep():
    pool = kvcache.StagedPagePool(6, [(0, 2), (2, 4)])
    assert [p.layers for p in pool.stage_pools] == [(0, 2), (2, 4)]
    a, b = pool.alloc(), pool.alloc()
    assert (a, b) == (1, 2)                     # deterministic order
    assert pool.used_pages == 2 and pool.free_pages == 3
    pool.ref(a)
    assert pool.refcount(a) == 2
    assert all(p.refcount(a) == 2 for p in pool.stage_pools)
    assert pool.unref(a) is False and pool.unref(a) is True
    assert pool.unref(b) is True
    assert pool.used_pages == 0
    assert all(p.used_pages == 0 for p in pool.stage_pools)


def test_staged_prefix_index_matches_and_evicts_across_stages():
    idx = kvcache.StagedPrefixIndex(4, [(0, 2), (2, 4), (4, 6)])
    prompt = list(range(12))
    new = idx.insert(prompt, [5, 6, 7], now=1.0)
    assert [n.page for n in new] == [5, 6, 7]
    assert idx.nodes == 3
    assert all(t.nodes == 3 for t in idx.stage_tries)
    pages, matched = idx.match(prompt + [99], now=2.0)
    assert pages == [5, 6, 7] and matched == 12
    assert idx.hits == 1 and all(t.hits == 1 for t in idx.stage_tries)
    leaf = idx.leaves()[0]
    assert idx.remove(leaf) == 7
    assert idx.nodes == 2 and all(t.nodes == 2 for t in idx.stage_tries)
    # remaining chain still matches two blocks in every stage trie
    pages, matched = idx.match(prompt + [99], now=3.0)
    assert pages == [5, 6] and matched == 8


def test_pipelined_engine_uses_staged_pools_and_prefix_reuse():
    from repro.serving.sharded import PipelinedEngine
    cfg, params = _zoo("qwen2-1.5b")
    eng = PipelinedEngine(cfg, params, stage_cuts=(cfg.n_layers // 2,),
                          n_slots=2, max_seq_len=48, page_size=4)
    assert eng.paged
    assert isinstance(eng.page_pool, kvcache.StagedPagePool)
    assert isinstance(eng.prefix_index, kvcache.StagedPrefixIndex)
    prompt = [(7 * j) % (cfg.vocab_size - 1) + 1 for j in range(16)]
    outs = []
    for _ in range(2):                 # second run must hit the prefix trie
        eng.submit(Request(rid=len(outs), prompt=list(prompt),
                           max_new_tokens=4))
        while eng.step():
            pass
        outs.append(list(eng.finished[-1].generated))
    assert outs[0] == outs[1]
    assert eng.prefix_index.hits >= 1
    assert eng.release_all_pages() == 0          # nothing leaked
