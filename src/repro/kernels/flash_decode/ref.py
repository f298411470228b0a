"""Pure-jnp oracles for split-KV flash decode (contiguous and paged)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


def flash_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array) -> jax.Array:
    """One-token decode attention.

    q: (B, H, D); k, v: (B, S, H, D) (head-repeated); kv_len: (B,) valid
    prefix lengths.  Returns (B, H, D).
    """
    B, S, H, D = k.shape
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bhd,bkhd->bhk", q, k,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(S)[None, :] < kv_len[:, None]          # (B, S)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p.astype(v.dtype), v)


def paged_flash_decode_ref(q: jax.Array, kp: jax.Array, vp: jax.Array,
                           ptab: jax.Array, kv_len: jax.Array,
                           window: Optional[int] = None) -> jax.Array:
    """Oracle for paged decode: gather pages to a contiguous view, mask,
    softmax.  q: (B, H, D); kp, vp: (P, Hkv, page, D); ptab: (B, n_ptab);
    kv_len: (B,).  GQA handled by head repetition (oracle only — the kernel
    never materialises the repeat)."""
    P, Hkv, page, D = kp.shape
    B, H, _ = q.shape
    S = ptab.shape[1] * page
    k = kp[ptab].swapaxes(2, 3).reshape(B, S, Hkv, D)         # gather pages
    v = vp[ptab].swapaxes(2, 3).reshape(B, S, Hkv, D)
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bhd,bkhd->bhk", q, k,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(S)[None, :]
    valid = kpos < kv_len[:, None]                            # (B, S)
    if window is not None:
        valid &= kpos >= jnp.maximum(kv_len[:, None] - window, 0)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p.astype(v.dtype), v)
