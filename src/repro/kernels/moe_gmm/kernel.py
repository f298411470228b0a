"""Grouped MoE SwiGLU matmul — Pallas TPU kernel.

Capacity-buffered expert FFN: x (E, C, D) × per-expert weights.  Grid =
(E, C/bc, F/bf): for each expert tile, the gate/up matmuls, SiLU and the
partial down-projection fuse in VMEM; the F-loop (last grid axis, sequential
on TPU) accumulates the down-projection in an f32 scratch accumulator —
the (C, F) intermediate never hits HBM.  Tiles default to (128, 512): gate/up
weight tiles are (D, 512), MXU-aligned.  Weight tiles stay in their storage
dtype (bf16 on the served path) and every matmul accumulates in f32 via
``preferred_element_type``; the scoped-VMEM limit is raised to what the
double-buffered tiles need (three (D, 512) bf16 tiles at Mixtral's D=4096
are 12 MiB, 24 MiB double-buffered — over the 16 MiB default on v5e).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_scr):
    fi = pl.program_id(2)
    nf = pl.num_programs(2)

    @pl.when(fi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(wg_ref.dtype)                      # (bc, D)
    g = jax.lax.dot_general(x, wg_ref[0], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    u = jax.lax.dot_general(x, wu_ref[0], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(wd_ref.dtype)          # (bc, bf)
    acc_scr[...] += jax.lax.dot_general(h, wd_ref[0], (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(fi == nf - 1)
    def _flush():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20    # v5e's scoped-VMEM default
_MAX_SCOPED_VMEM = 96 * 2 ** 20        # leaves headroom in v5e's 128 MiB


def _vmem_bytes(bc: int, bf: int, D: int, x_itemsize: int,
                w_itemsize: int) -> int:
    """Scoped VMEM one grid step needs: double-buffered x/out and
    gate/up/down weight tiles, the f32 accumulator and the (bc, bf) f32
    intermediates."""
    tiles = 2 * (2 * bc * D * x_itemsize + 3 * D * bf * w_itemsize)
    return tiles + bc * D * 4 + 3 * bc * bf * 4


def moe_gmm_kernel(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                   w_down: jax.Array, block_c: int = 128, block_f: int = 512,
                   interpret: bool = False) -> jax.Array:
    """x: (E, C, D); w_gate/w_up: (E, D, F); w_down: (E, F, D) → (E, C, D)."""
    E, C, D = x.shape
    F = w_gate.shape[-1]
    bc = min(block_c, C)
    bf = min(block_f, F)
    assert C % bc == 0 and F % bf == 0, (C, bc, F, bf)
    need = _vmem_bytes(bc, bf, D, x.dtype.itemsize, w_gate.dtype.itemsize)
    if need > _MAX_SCOPED_VMEM:
        raise ValueError(
            f"moe_gmm tiles (block_c={bc}, block_f={bf}, D={D}) need "
            f"{need / 2**20:.1f} MiB of VMEM; pass a smaller block_f")
    # a margin over the counted buffers covers Mosaic's internal scratch
    limit = max(_DEFAULT_SCOPED_VMEM, min(need + need // 4 + 2 ** 20,
                                          _MAX_SCOPED_VMEM))
    return pl.pallas_call(
        _moe_kernel,
        grid=(E, C // bc, F // bf),
        in_specs=[
            pl.BlockSpec((1, bc, D), lambda e, ci, fi: (e, ci, 0)),
            pl.BlockSpec((1, D, bf), lambda e, ci, fi: (e, 0, fi)),
            pl.BlockSpec((1, D, bf), lambda e, ci, fi: (e, 0, fi)),
            pl.BlockSpec((1, bf, D), lambda e, ci, fi: (e, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, D), lambda e, ci, fi: (e, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
    )(x, w_gate, w_up, w_down)
