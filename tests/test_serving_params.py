"""The served weight tree (``lm.serving_params``): matrices, biases and the
embedding held in the compute dtype give steps bit-identical to the float32
tree they were rounded from, and an engine's step takes no float32 matrix."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import get_config, list_archs
from repro.models import lm
from repro.serving.engine import Engine, Request

# every leaf the forward reads only as ``w.astype(x.dtype)``
CAST = {"embed", "lm_head", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
        "wq_a", "wq_b", "wkv_a", "wk_b", "wv_b",
        "w_gate", "w_up", "w_down", "router"}
PAGED = ("qwen2-1.5b", "mixtral-8x7b", "minicpm3-4b")
UNPAGED = ("mamba2-1.3b", "zamba2-7b")
PAGE, B, C = 4, 2, 8


def _role(path):
    return path[-1].key


def _setup(arch):
    """The reduced config and its float32 weights, with norm scales drawn
    away from init's zeros so that rounding one would change the logits."""
    cfg = get_config(arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    return cfg, jax.tree_util.tree_map_with_path(
        lambda p, x: (jax.random.uniform(key, x.shape, x.dtype, -0.3, 0.3)
                      if _role(p) == "scale" else x), params)


def _assert_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _tokens(cfg, shape, seed):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 1,
                              cfg.vocab_size, jnp.int32)


@pytest.mark.parametrize("arch", list_archs())
def test_listed_leaves_take_the_compute_dtype(arch):
    """Each listed leaf comes out in ``cfg.dtype``; norm scales, SSM
    parameters and every other leaf keep init's float32."""
    cfg = get_config(arch).reduced()
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    served = jax.eval_shape(lambda: lm.serving_params(
        cfg, lm.init_params(cfg, jax.random.PRNGKey(0))))
    assert cfg.dtype == "bfloat16"
    n_cast = 0
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(shapes),
                            jax.tree.leaves(served)):
        assert x.dtype == jnp.float32 and y.shape == x.shape
        if _role(path) in CAST:
            assert y.dtype == jnp.bfloat16, path
            n_cast += 1
        else:
            assert y.dtype == jnp.float32, path
    assert n_cast > 0


def test_served_tree_passes_through_and_float32_changes_nothing():
    cfg, params = _setup("qwen2-1.5b")
    served = lm.serving_params(cfg, params)
    again = lm.serving_params(cfg, served)
    assert all(a is b for a, b in zip(jax.tree.leaves(served),
                                      jax.tree.leaves(again)))
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    same = lm.serving_params(cfg32, params)
    assert all(a is b for a, b in zip(jax.tree.leaves(params),
                                      jax.tree.leaves(same)))


@pytest.mark.parametrize("arch", PAGED)
def test_paged_step_bit_identical(arch):
    """A prefill chunk then a decode step through ``lm.paged_step``: the
    served tree's logits and pool equal the float32 tree's exactly."""
    cfg, params = _setup(arch)
    served = lm.serving_params(cfg, params)
    pps = 4
    ptab = jnp.asarray(1 + np.arange(B * pps, dtype=np.int32).reshape(B, pps))
    act = jnp.ones((B,), bool)
    step = jax.jit(lambda p, c, t, pos2: lm.paged_step(
        p, cfg, c, t, pos2, ptab, act, page_size=PAGE))

    def run(p):
        cache = lm.init_paged_cache(cfg, 1 + B * pps, PAGE,
                                    dtype=jnp.bfloat16)
        pos = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
        l1, cache = step(p, cache, _tokens(cfg, (B, C), 1), pos)
        l2, cache = step(p, cache, _tokens(cfg, (B, 1), 2),
                         jnp.full((B, 1), C, jnp.int32))
        return l1, l2, cache

    _assert_equal(run(params), run(served))


@pytest.mark.parametrize("arch", UNPAGED)
def test_contiguous_steps_bit_identical(arch):
    """``prefill_step`` then ``decode_step`` on the contiguous cache (the
    SSM and hybrid families): logits and state equal exactly."""
    cfg, params = _setup(arch)
    served = lm.serving_params(cfg, params)
    prefill = jax.jit(lambda p, c, t, pos: lm.prefill_step(p, cfg, c, t, pos))
    decode = jax.jit(lambda p, c, t, pos: lm.decode_step(p, cfg, c, t, pos))

    def run(p):
        cache = lm.init_cache(cfg, B, 32, dtype=jnp.bfloat16)
        pos = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
        l1, cache = prefill(p, cache, _tokens(cfg, (B, C), 1), pos)
        l2, cache = decode(p, cache, _tokens(cfg, (B, 1), 2),
                           jnp.full((B,), C, jnp.int32))
        return l1, l2, cache

    _assert_equal(run(params), run(served))


@pytest.mark.parametrize("arch", PAGED + UNPAGED[:1])
def test_engine_tokens_identical(arch):
    """An engine serving the float32 tree (as every step once converted it)
    and one serving its own cast tree emit the same greedy tokens."""
    cfg, params = _setup(arch)

    def serve(eng):
        for rid, n in ((0, 9), (1, 5), (2, 13)):
            eng.submit(Request(rid=rid, prompt=[1 + (rid * 7 + j) % 50
                                                for j in range(n)],
                               max_new_tokens=6))
        eng.run_until_drained()
        return {st.request.rid: st.generated for st in eng.finished}

    eng = Engine(cfg, params, n_slots=2, max_seq_len=32, page_size=PAGE)
    ref = Engine(cfg, params, n_slots=2, max_seq_len=32, page_size=PAGE)
    ref.params = params
    assert eng.params_cast > 0
    assert serve(eng) == serve(ref)


def test_paged_exec_takes_no_float32_matrix():
    """Lowered on the engine's own tree, the decode step's float32 arguments
    are the norm scales and nothing else."""
    cfg, params = _setup("qwen2-1.5b")
    eng = Engine(cfg, params, n_slots=B, max_seq_len=32, page_size=PAGE)
    pps = eng._ptab.shape[1]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    lowered = eng._paged_exec.lower(eng.params, eng.cache, i32(B, 1),
                                    i32(B, 1), i32(B, pps),
                                    jax.ShapeDtypeStruct((B,), jnp.bool_))
    args = jax.tree.leaves(lowered.in_avals)
    f32 = [a for a in args if a.dtype == jnp.float32]
    norms = [x for p, x in jax.tree_util.tree_leaves_with_path(params)
             if _role(p) == "scale"]
    assert sorted(a.shape for a in f32) == sorted(x.shape for x in norms)
    assert all(len(a.shape) <= 2 for a in f32)      # (d,) or (L, d) only
    assert (sum(a.size * 4 for a in f32)
            == sum(x.size * 4 for x in norms))

    # counters: 11 leaves cast (tied head, QKV bias, dense FFN) and the
    # served bytes are those leaves at 2 bytes plus the norms at 4
    assert eng.params_cast == 11
    cast = [x for p, x in jax.tree_util.tree_leaves_with_path(params)
            if _role(p) in CAST]
    assert len(cast) == 11
    assert eng.param_bytes == (sum(x.size * 2 for x in cast)
                               + sum(x.size * 4 for x in norms))
    f32_bytes = sum(x.size * 4 for x in jax.tree.leaves(params))
    assert eng.param_bytes < 0.51 * f32_bytes


def test_engine_handed_a_served_tree_casts_nothing():
    cfg, params = _setup("qwen2-1.5b")
    served = lm.serving_params(cfg, params)
    eng = Engine(cfg, served, n_slots=2, max_seq_len=32, page_size=PAGE)
    assert eng.params_cast == 0
    assert all(a is b for a, b in zip(jax.tree.leaves(served),
                                      jax.tree.leaves(eng.params)))


def test_backend_holds_one_served_tree():
    """``JaxBackend`` casts once; the engines a plan builds reuse its leaves
    and cast nothing."""
    from repro.core.plan import Plan, ReplicaGroup
    from repro.serving.backend import JaxBackend
    cfg, params = _setup("qwen2-1.5b")
    backend = JaxBackend(cfg, params, max_seq_len=32, slots_cap=2,
                         shard_replicas=False)
    assert backend.params_cast == 11
    backend.apply_plan(Plan((ReplicaGroup(cfg.name, "TPU-v5e", tp=1, batch=2,
                                          count=1),)), None)
    (eng,) = backend.pool.engines
    assert eng.params_cast == 0
    assert all(a is b for a, b in zip(jax.tree.leaves(backend.params),
                                      jax.tree.leaves(eng.params)))


def test_sharded_and_pipelined_engines_serve_the_cast_tree():
    """The mesh replica device-puts the cast tree (one-device mesh here) and
    the pipeline's stages are sliced from it."""
    from repro.serving.sharded import PipelinedEngine, ShardedEngine
    cfg, params = _setup("qwen2-1.5b")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    sharded = ShardedEngine(cfg, params, mesh, n_slots=2, max_seq_len=32,
                            page_size=PAGE)
    piped = PipelinedEngine(cfg, params, (cfg.n_layers // 2,), n_slots=2,
                            max_seq_len=32, page_size=PAGE)
    for eng in (sharded, piped):
        assert eng.params_cast == 11
        for path, x in jax.tree_util.tree_leaves_with_path(eng.params):
            want = jnp.bfloat16 if _role(path) in CAST else jnp.float32
            assert x.dtype == want, path
