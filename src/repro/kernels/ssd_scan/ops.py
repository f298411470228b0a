"""jit'd public wrapper for the SSD scan kernel."""
import functools
from typing import Optional

import jax

from repro.kernels import default_interpret
from repro.kernels.ssd_scan.kernel import ssd_scan_kernel
from repro.kernels.ssd_scan.ref import ssd_scan_ref


@functools.partial(jax.jit, static_argnames=("chunk", "block_h", "interpret"))
def ssd_scan(x, dt, A, B, C, chunk: int = 64, block_h: int = 0,
             interpret: Optional[bool] = None):
    """Returns y only (state handling stays in the model layer)."""
    if interpret is None:
        interpret = default_interpret()
    return ssd_scan_kernel(x, dt, A, B, C, chunk=chunk, block_h=block_h,
                           interpret=interpret)


def reference(x, dt, A, B, C, chunk: int = 64):
    y, _ = ssd_scan_ref(x, dt, A, B, C, chunk=chunk)
    return y
