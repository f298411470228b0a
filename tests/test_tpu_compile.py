"""The served path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: JAX can describe a ``v5e:2x2`` topology and the TPU
compiler lowers for it, so alignment and VMEM limits that interpret mode
never checks are enforced here.  Nothing runs; these tests only compile.
The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the one that is given
this file loads the TPU library.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.kernels.flash_decode import ops as fd_ops
from repro.kernels.flash_decode.kernel import paged_flash_decode_kernel
from repro.kernels.moe_gmm.kernel import moe_gmm_kernel

HBM_BYTES = 16 * 10 ** 9          # one TPU v5e chip
SLOTS, MAX_SEQ, PAGE = 8, 1024, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip; keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _n_pages(slots=SLOTS):
    # the engine's default pool: trash page + (slots + 2) slots' worth
    return 1 + (slots + 2) * (MAX_SEQ // PAGE)


def _decode_shapes(cfg, sharding, kv_spec=None):
    """Shapes of one paged decode call at the served widths."""
    def sds(shape, dt, spec=None):
        sh = sharding if spec is None else NamedSharding(sharding.mesh, spec)
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    pool = (_n_pages(), cfg.n_kv_heads, PAGE, cfg.d_head)
    q = sds((SLOTS, cfg.n_heads, cfg.d_head), jnp.bfloat16,
            None if kv_spec is None else P())
    kp = sds(pool, jnp.bfloat16, kv_spec)
    ptab = sds((SLOTS, MAX_SEQ // PAGE), jnp.int32,
               None if kv_spec is None else P())
    lens = sds((SLOTS,), jnp.int32, None if kv_spec is None else P())
    return q, kp, kp, ptab, lens


def test_paged_decode_kernel_compiles_at_qwen2_widths(one_chip):
    cfg = get_config("qwen2-1.5b")
    fn = functools.partial(paged_flash_decode_kernel, interpret=False)
    compiled = jax.jit(fn).lower(*_decode_shapes(cfg, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch,tp", [("qwen2-1.5b", 2), ("mixtral-8x7b", 4)])
def test_sharded_paged_decode_compiles_under_shard_map(topo, arch, tp):
    """The head-slice form each shard runs: qwen2 (2 KV heads, one per
    shard at tp=2) and mixtral (8 KV heads, two per shard at tp=4)."""
    cfg = get_config(arch)
    mesh = Mesh(np.array(topo.devices[:tp]), ("model",))
    shapes = _decode_shapes(cfg, NamedSharding(mesh, P()),
                            kv_spec=P(None, "model", None, None))
    window = cfg.sliding_window

    def fn(q, kp, vp, ptab, lens):
        return fd_ops.sharded_paged_flash_decode(
            q, kp, vp, ptab, lens, mesh, axis="model", window=window,
            interpret=False)
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_moe_gmm_compiles_at_mixtral_ep_shard_widths(one_chip):
    """One EP shard of mixtral-8x7b under tp=4: 2 local experts, a decode
    batch of 8 tokens, D=4096, F=14336, bf16 weights as the EP path casts
    them, at the block sizes ``ep_moe_mix`` passes."""
    cfg = get_config("mixtral-8x7b")
    e, c, d, f = cfg.n_experts // 4, SLOTS, cfg.d_model, cfg.d_ff

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    fn = functools.partial(moe_gmm_kernel, block_c=c, block_f=512,
                           interpret=False)
    compiled = jax.jit(fn).lower(sds((e, c, d)), sds((e, d, f)),
                                 sds((e, d, f)), sds((e, f, d))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_qwen2_decode_step_fits_one_chip(one_chip):
    """The whole served decode step — 28 layers at published widths with
    the fused kernel — compiles for one v5e and fits its 16 GB, on the
    float32 tree ``init_params`` draws and on the tree an engine serves
    (``serving_params``: matrices in bfloat16), whose arguments are about
    half the float32 tree's."""
    from repro.models import lm
    cfg = get_config("qwen2-1.5b")
    params = jax.eval_shape(lambda k: lm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    served = jax.eval_shape(lambda k: lm.serving_params(
        cfg, lm.init_params(cfg, k)), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_paged_cache(
        cfg, _n_pages(), PAGE, dtype=jnp.bfloat16))

    def place(t):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), t)

    def i32(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(p, c, t, pos2, ptab, act):
        return lm.paged_step(p, cfg, c, t, pos2, ptab, act, page_size=PAGE,
                             use_kernel=True, interpret=False)
    args, converts = {}, {}
    for name, tree in (("float32", params), ("served", served)):
        compiled = jax.jit(step).lower(
            place(tree), place(cache), i32((SLOTS, 1)), i32((SLOTS, 1)),
            i32((SLOTS, MAX_SEQ // PAGE)),
            jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=one_chip)
        ).compile()
        assert "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        assert total < HBM_BYTES, (f"{name} decode step needs "
                                   f"{total / 1e9:.2f} GB")
        args[name] = mem.argument_size_in_bytes
        # an op that rounds a stacked (per-layer) weight inside the step
        converts[name] = len(re.findall(
            rf"%convert[\w.]* = bf16\[{cfg.n_layers},", compiled.as_text()))
    assert converts["float32"] > 0 and converts["served"] == 0, converts
    assert args["served"] <= 4.3e9, f"served args {args['served'] / 1e9:.2f} GB"
    assert args["served"] < 0.55 * args["float32"], args
