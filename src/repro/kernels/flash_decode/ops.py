"""jit'd public wrappers for flash decode (GQA-aware, contiguous + paged).

``interpret`` defaults from the backend (:func:`repro.kernels.default_interpret`):
the Pallas interpreter is a debugging aid, not a serving path — on TPU the
compiled kernel runs, elsewhere interpret mode keeps the kernels testable.
GQA grouping lives inside the kernels; nothing here materialises repeated
K/V.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels import default_interpret
from repro.kernels.flash_decode.kernel import (flash_decode_kernel,
                                               paged_flash_decode_kernel)
from repro.kernels.flash_decode.ref import (flash_decode_ref,
                                            paged_flash_decode_ref)


def _repeat_kv(q, k, v):
    H, Hkv = q.shape[-2], k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def _flash_decode(q, k, v, kv_len, block_k: int, interpret: bool):
    return flash_decode_kernel(q, k, v, kv_len, block_k=block_k,
                               interpret=interpret)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, kv_len: jax.Array,
                 block_k: int = 512,
                 interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, D); k, v: (B, S, Hkv, D) un-repeated; kv_len: (B,)."""
    if interpret is None:
        interpret = default_interpret()
    return _flash_decode(q, k, v, kv_len, block_k, interpret)


def paged_flash_decode_head_slice(q: jax.Array, kp: jax.Array, vp: jax.Array,
                                  ptab: jax.Array, kv_len: jax.Array,
                                  kv_head_offset, total_kv_heads: int,
                                  window: Optional[int] = None,
                                  interpret: Optional[bool] = None
                                  ) -> jax.Array:
    """Fused paged decode over one contiguous KV-head slice — the single
    kernel wrapper shared by the unsharded path and each shard_map shard.

    ``q`` carries the FULL head set (B, H, D); ``kp``/``vp`` carry exactly
    this slice's KV heads (P, Hkv_slice, page, D) — the whole pool on one
    device, or a shard's local pool slice under shard_map.
    ``kv_head_offset`` counts KV heads (may be traced, e.g. ``axis_index``
    inside shard_map) and selects the matching GQA q-head block
    ``[offset*G, (offset + Hkv_slice)*G)`` so group mapping stays
    slice-local.  Returns that block's outputs (B, G*Hkv_slice, D).
    """
    B, H, D = q.shape
    hkv_slice = kp.shape[1]
    if total_kv_heads <= 0 or H % total_kv_heads != 0:
        raise ValueError(
            f"GQA grouping needs n_heads ({H}) divisible by total KV heads "
            f"({total_kv_heads}): paged flash-decode cannot map query heads "
            f"onto KV-head slices otherwise")
    G = H // total_kv_heads
    q_slice = jax.lax.dynamic_slice_in_dim(
        q, kv_head_offset * G, hkv_slice * G, axis=1)
    if interpret is None:
        interpret = default_interpret()
    return paged_flash_decode_kernel(q_slice, kp, vp,
                                     ptab.astype(jnp.int32),
                                     kv_len.astype(jnp.int32),
                                     window=window, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _paged_flash_decode(q, kp, vp, ptab, kv_len, window, interpret):
    return paged_flash_decode_head_slice(q, kp, vp, ptab, kv_len, 0,
                                         kp.shape[1], window=window,
                                         interpret=interpret)


def paged_flash_decode(q: jax.Array, kp: jax.Array, vp: jax.Array,
                       ptab: jax.Array, kv_len: jax.Array,
                       window: Optional[int] = None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Paged decode: q (B, H, D); kp/vp (P, Hkv, page, D); ptab (B, n_ptab)
    logical-block → physical-page; kv_len (B,).  The page table is gathered
    inside the kernel via scalar prefetch."""
    if interpret is None:
        interpret = default_interpret()
    return _paged_flash_decode(q, kp, vp, ptab, kv_len, window, interpret)


def sharded_paged_flash_decode(q: jax.Array, kp: jax.Array, vp: jax.Array,
                               ptab: jax.Array, kv_len: jax.Array, mesh,
                               axis: str = "model",
                               window: Optional[int] = None,
                               interpret: Optional[bool] = None) -> jax.Array:
    """Fused paged decode under an explicit shard_map over the head-sharded
    page pool.

    ``pallas_call`` has no GSPMD partition rule, so the fused kernel cannot
    run inside a partitioned jit directly; instead (mirroring the EP
    ``moe_gmm`` path) each shard of the ``axis``-sharded pool runs the
    kernel over its OWN KV-head slice through the replicated page-table and
    length scalars.  GQA group mapping stays shard-local because q-head
    block i*H/tp maps exactly onto KV-head block i*Hkv/tp, and the outputs
    concatenate along heads — token-identical to the unfused paged gather
    path (and the unsharded kernel), no combine collective needed.
    """
    if interpret is None:
        interpret = default_interpret()
    tp = mesh.shape[axis]
    hkv = kp.shape[1]
    if hkv % tp != 0:
        raise ValueError(
            f"n_kv_heads={hkv} not divisible by tp={tp} on axis {axis!r}; "
            f"the sharded engine must fall back to the unfused paged path "
            f"(and record the fallback) for this config")
    local = hkv // tp

    def local_decode(qf, kp_l, vp_l, pt, kl):
        # qf (B, H, D) replicated; kp_l/vp_l (P, Hkv/tp, page, D) local
        off = jax.lax.axis_index(axis) * local
        return paged_flash_decode_head_slice(qf, kp_l, vp_l, pt, kl, off,
                                             hkv, window=window,
                                             interpret=interpret)

    in_specs = (P(), P(None, axis, None, None), P(None, axis, None, None),
                P(), P())
    # check_vma=False: pallas_call has no replication rule; outputs
    # concatenate along the shard axis in head order (no psum)
    return shard_map(local_decode, mesh=mesh, in_specs=in_specs,
                     out_specs=P(None, axis, None), check_vma=False)(
                         q, kp, vp, ptab, kv_len)


def reference(q, k, v, kv_len):
    k, v = _repeat_kv(q, k, v)
    return flash_decode_ref(q, k, v, kv_len)


def paged_reference(q, kp, vp, ptab, kv_len, window=None):
    return paged_flash_decode_ref(q, kp, vp, ptab, kv_len, window=window)
