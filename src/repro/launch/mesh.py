"""Production mesh construction (function, not module constant — importing
this module never touches jax device state)."""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import numpy as np


def make_mesh_compat(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated), the
    sharding mode the repo's pjit rules are written for."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16 data × 16 model). Multi-pod: 2 × 256 = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    data = n // model_axis
    return make_mesh_compat((data, model_axis), ("data", "model"))


def carve_submeshes(mesh: jax.sharding.Mesh,
                    shapes: Sequence[Tuple[int, ...]],
                    axes: Tuple[str, ...] = ("pipe", "data", "model")):
    """Partition ``mesh``'s devices into per-replica submeshes.

    Deterministic: devices are consumed in sorted-id order, so the same
    (mesh, shapes) always yields the same physical assignment — shadow
    replay and the pool's diff/rebuild both depend on that.  Shapes map
    onto the TRAILING axis names: a 2-D shape becomes a ``(data, model)``
    submesh, a 3-D shape ``(pipe, data, model)`` — the replica-level mesh
    of a pipelined group.  Raises ``ValueError`` when the requested shapes
    oversubscribe the mesh (the caller — usually the pool's
    :class:`~repro.serving.sharded.SubmeshAllocator` — decides whether to
    fall back to smaller shapes).
    """
    devices = sorted(mesh.devices.flatten().tolist(), key=lambda d: d.id)
    need = sum(int(np.prod(s)) for s in shapes)
    if need > len(devices):
        raise ValueError(
            f"carve_submeshes: shapes {list(shapes)} need {need} devices "
            f"but the mesh has {len(devices)}")
    out, off = [], 0
    for s in shapes:
        n = int(np.prod(s))
        grid = np.array(devices[off:off + n], dtype=object).reshape(s)
        out.append(jax.sharding.Mesh(grid, axes[-len(s):]))
        off += n
    return out
