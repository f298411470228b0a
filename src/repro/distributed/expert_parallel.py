"""Expert-parallel MoE FFN under shard_map.

The expert axis is sharded on the mesh's tensor-parallel axis: each shard
holds ``E / tp`` whole experts and runs them through the grouped
:func:`repro.kernels.moe_gmm.ops.moe_gmm` matmul; the gate-weighted partial
outputs are combined with a ``psum``.  Semantics are exactly the dense-mix
baseline (every token visits every expert, no capacity dropping), so a
sharded engine produces token-identical outputs to the unsharded one —
the parity contract the sharded serving tests assert.

Gating runs replicated (router weights are small) so all shards agree on
the gates bit-for-bit; only the expert FFN work is partitioned.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.kernels.moe_gmm.ops import moe_gmm
from repro.models.layers import moe_gates


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def ep_moe_mix(p, cfg, x: jax.Array, mesh: Mesh,
               axis: str = "model") -> jax.Array:
    """Expert-parallel dense-mix MoE: shard_map over the expert axis.

    ``p`` holds the full (replicated-or-sharded) MoE params; under a
    sharded engine the expert-axis weights are already placed with
    ``P(axis)`` so shard_map binds each shard's local experts without any
    gather.  Works for any divisible expert count; token count is padded to
    the moe_gmm block size when needed.
    """
    B, S, d = x.shape
    ep = mesh.shape[axis]
    e_total = p["w_gate"].shape[0]
    if e_total % ep != 0:
        raise ValueError(f"n_experts={e_total} not divisible by "
                         f"expert-parallel degree {ep}")
    gates = moe_gates(p, cfg, x)                       # (B,S,E) f32
    dtype = x.dtype
    wg = p["w_gate"].astype(dtype)
    wu = p["w_up"].astype(dtype)
    wd = p["w_down"].astype(dtype)

    tokens = B * S
    block_c = tokens if tokens <= 128 else _round_up(tokens, 128)

    def local_mix(xb, gb, wg_l, wu_l, wd_l):
        # xb (B,S,d) replicated; gb (B,S,E/ep); w*_l (E/ep, ...) local experts
        e_loc = wg_l.shape[0]
        xt = xb.reshape(1, tokens, d)
        if block_c != tokens:              # pad to the kernel's block size
            xt = jnp.pad(xt, ((0, 0), (0, block_c - tokens), (0, 0)))
        xe = jnp.broadcast_to(xt, (e_loc, xt.shape[1], d))
        f = wg_l.shape[-1]
        y = moe_gmm(xe, wg_l, wu_l, wd_l,
                    block_c=min(block_c, 128), block_f=min(f, 512))
        y = y[:, :tokens, :].reshape(e_loc, B, S, d)
        out = jnp.einsum("ebsd,bse->bsd", y, gb.astype(dtype))
        return jax.lax.psum(out, axis)

    in_specs = (P(), P(None, None, axis), P(axis), P(axis), P(axis))
    # check_vma=False: pallas_call has no replication rule; the psum above
    # makes the output replicated by construction
    return shard_map(local_mix, mesh=mesh, in_specs=in_specs,
                     out_specs=P(), check_vma=False)(x, gates, wg, wu, wd)
