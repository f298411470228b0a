"""Flash decode — split-KV one-token attention, Pallas TPU kernels.

Two entry points:

  * :func:`flash_decode_kernel` — contiguous KV.  Grid = (B·Hkv, S/bk):
    sequential kv blocks accumulate partial softmax state in VMEM scratch
    (FlashDecoding-style rescale-combine).  GQA is handled *in-kernel*: the
    grid iterates kv heads and each program attends its whole q-head group
    (G = H/Hkv rows) against one un-repeated K/V stream, so no
    ``jnp.repeat``-materialised copies ever hit HBM.
  * :func:`paged_flash_decode_kernel` — block-paged KV.  K/V live in a
    shared page pool ``(P, Hkv, page, D)``; the per-sequence page table is a
    scalar-prefetch operand so the BlockSpec index_map gathers the right
    physical page per kv block *inside* the kernel (one kv block == one
    page of one KV head, a ``(page, D)`` tile — the TPU lowering needs a
    block's last two dims to be whole array dims or (8, 128)-aligned).
    Optional sliding-window masking supports paged SWA caches, which keep
    all positions and mask instead of ring-rotating.

Valid-length masking supports ragged KV prefixes (continuous batching).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, bk: int):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[0]
    k_start = ki * bk

    @pl.when(k_start < kv_len)
    def _body():
        q = q_ref[0].astype(jnp.float32)                 # (G, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)        # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)          # (G, bk)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(kpos < kv_len, jnp.exp(s - m_new), 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * alpha
                        + jax.lax.dot_general(
                            p, v_ref[0, :, 0, :].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_decode_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                        kv_len: jax.Array, block_k: int = 512,
                        interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k, v: (B, S, Hkv, D) un-repeated; kv_len: (B,) int32.

    GQA grouping stays inside the kernel: grid axis 0 walks (batch × kv
    head) and the q block carries the whole G = H/Hkv query group.
    """
    B, S, Hkv, D = k.shape
    H = q.shape[1]
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    bk = min(block_k, S)
    assert S % bk == 0
    scale = 1.0 / math.sqrt(D)

    qf = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    lens = kv_len.astype(jnp.int32)                       # (B,)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bk=bk),
        grid=(B * Hkv, S // bk),
        in_specs=[
            pl.BlockSpec((1,), lambda i, ki: (i // Hkv,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, G, D), lambda i, ki: (i, 0, 0)),
            pl.BlockSpec((1, bk, 1, D), lambda i, ki: (i // Hkv, ki, i % Hkv, 0)),
            pl.BlockSpec((1, bk, 1, D), lambda i, ki: (i // Hkv, ki, i % Hkv, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda i, ki: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(lens, qf, k, v)
    return out.reshape(B, H, D)


# --------------------------------------------------------------------------- #
# paged flash decode
# --------------------------------------------------------------------------- #
def _paged_decode_kernel(ptab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale: float, page: int,
                         hkv: int, window: Optional[int]):
    i = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    b = i // hkv

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[b]
    k_start = ki * page
    lo = jnp.int32(0) if window is None else jnp.maximum(kv_len - window, 0)
    live = jnp.logical_and(k_start < kv_len, k_start + page > lo)

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)                 # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (page, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = jnp.logical_and(kpos < kv_len, kpos >= lo)
        s = jnp.where(ok, s, NEG_INF)                     # (G, page)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * alpha
                        + jax.lax.dot_general(
                            p, v_ref[0, 0].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_flash_decode_kernel(q: jax.Array, kp: jax.Array, vp: jax.Array,
                              ptab: jax.Array, kv_len: jax.Array,
                              window: Optional[int] = None,
                              interpret: bool = False) -> jax.Array:
    """One-token decode attention over a block-paged KV pool.

    q: (B, H, D); kp, vp: (P, Hkv, page, D) shared physical page pools;
    ptab: (B, n_ptab) int32 logical-block → physical-page map (0 = trash
    page for unmapped blocks); kv_len: (B,) valid tokens per sequence.

    The page table and lengths ride the scalar-prefetch path so the K/V
    BlockSpec index_maps dereference ``ptab`` on-device — the kernel streams
    exactly the pages a sequence owns, never a contiguous copy.  Grid axis 0
    walks (batch × kv head); the q block is that head's whole GQA group.
    """
    P, Hkv, page, D = kp.shape
    B, H, _ = q.shape
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    n_ptab = ptab.shape[1]
    scale = 1.0 / math.sqrt(D)

    qf = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * Hkv, n_ptab),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda i, ki, pt, kl: (i, 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda i, ki, pt, kl: (pt[i // Hkv, ki], i % Hkv,
                                                0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda i, ki, pt, kl: (pt[i // Hkv, ki], i % Hkv,
                                                0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda i, ki, pt, kl: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, page=page,
                          hkv=Hkv, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        interpret=interpret,
        name="paged_flash_decode",
    )(ptab.astype(jnp.int32), kv_len.astype(jnp.int32), qf, kp, vp)
    return out.reshape(B, H, D)
