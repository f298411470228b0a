"""jit'd public wrapper for the grouped MoE SwiGLU matmul."""
import functools
from typing import Optional

import jax

from repro.kernels import default_interpret
from repro.kernels.moe_gmm.kernel import moe_gmm_kernel
from repro.kernels.moe_gmm.ref import moe_gmm_ref


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "interpret"))
def moe_gmm(x, w_gate, w_up, w_down, block_c: int = 128, block_f: int = 512,
            interpret: Optional[bool] = None):
    if interpret is None:
        interpret = default_interpret()
    return moe_gmm_kernel(x, w_gate, w_up, w_down, block_c=block_c,
                          block_f=block_f, interpret=interpret)


reference = moe_gmm_ref
