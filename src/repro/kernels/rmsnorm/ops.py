"""jit'd public wrapper for fused RMSNorm."""
import functools
from typing import Optional

import jax

from repro.kernels import default_interpret
from repro.kernels.rmsnorm.kernel import rmsnorm_kernel
from repro.kernels.rmsnorm.ref import rmsnorm_ref


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, eps: float = 1e-6, block_rows: int = 256,
            interpret: Optional[bool] = None):
    if interpret is None:
        interpret = default_interpret()
    return rmsnorm_kernel(x, scale, eps=eps, block_rows=block_rows,
                          interpret=interpret)


reference = rmsnorm_ref
