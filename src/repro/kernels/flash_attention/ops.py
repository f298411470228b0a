"""jit'd public wrapper for the flash-attention kernel (GQA-aware)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """GQA flash attention. q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D)."""
    if interpret is None:
        interpret = default_interpret()
    H, Hkv = q.shape[2], k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return flash_attention_kernel(q, k, v, causal=causal, window=window,
                                  softcap=softcap, block_q=block_q,
                                  block_k=block_k, interpret=interpret)


def reference(q, k, v, causal=True, window=None, softcap=None):
    H, Hkv = q.shape[2], k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return flash_attention_ref(q, k, v, causal=causal, window=window,
                               softcap=softcap)
