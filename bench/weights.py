"""Seeded random weights, made by the benchmark and loaded into the program.

``make`` draws a configuration's weights from the seed in a plain layout
(the one :mod:`bench.reference.model` reads): ``embed`` (V, d),
``final_norm`` (d,), ``lm_head`` (d, V) when untied, and ``layers``, a dict
of each layer's tensors stacked on a leading axis.  Matrices are uniform in
``±1/sqrt(fan_in)``, except that the feed-forward's down projection is
scaled by ``FFN_OUT_GAIN`` and attention's output projection by
``ATTN_OUT_GAIN``; norm weights are ``1 + U(±0.2)`` and QKV biases
``U(±0.5)``, so that a path that skips either shows.  Everything is drawn
in one jitted call on the device, in float32, the type the program keeps
its weights in.  Where a cell serves across chips, the same call runs with
its output split over them (:func:`placement`): a four-layer Mixtral is
24 GB of float32, more than one chip holds.  JAX's threefry is
partitionable (``jax_threefry_partitionable``, on by default), so the values
are those of the one-device draw, bit for bit.

Why the two gains: with every matrix at ``±1/sqrt(fan_in)``, greedy
decoding of a random model falls within a few tokens onto one token that it
repeats, because attention averages the context into a vector that no
longer changes.  Its logit then leads the next by 0.5 to 1, so no rounding
error flips a served token and a comparison of served tokens could not
tell a lower precision from the stated one.  With the feed-forward, which
acts on each token alone, outweighing attention in the residual stream,
served sequences keep changing token (123 distinct tokens in 128 at 28
layers, measured at width 256) and the leading logit's margin is small
(median 0.08), as in a trained model's output.

``program_params`` maps the plain layout onto the program's parameter tree,
as a checkpoint loader would; the reference never sees that tree.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from bench.reference.model import Arch

NORM_SPREAD = 0.2
BIAS_SPREAD = 0.5
FFN_OUT_GAIN = 4.0
ATTN_OUT_GAIN = 0.3


def key_data(seed: int) -> np.ndarray:
    """A threefry key's two words from a seed of up to 64 bits."""
    s = int(seed) & (2 ** 64 - 1)
    return np.array([s >> 32, s & 0xFFFFFFFF], dtype=np.uint32)


def shapes(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], float, float]]:
    """name → (shape, low, high) of each plain tensor, in draw order."""
    d, L, H, Hkv, Dh = a.d, a.layers, a.heads, a.kv_heads, a.head_dim
    F, V, E = a.ffn, a.vocab, a.experts
    s = 1.0 / math.sqrt(d)
    out = {"embed": ((V, d), -s, s),
           "final_norm": ((d,), 1 - NORM_SPREAD, 1 + NORM_SPREAD)}
    if not a.tied:
        out["lm_head"] = ((d, V), -s, s)
    lay = {"attn_norm": ((L, d), 1 - NORM_SPREAD, 1 + NORM_SPREAD),
           "mlp_norm": ((L, d), 1 - NORM_SPREAD, 1 + NORM_SPREAD),
           "wq": ((L, d, H * Dh), -s, s),
           "wk": ((L, d, Hkv * Dh), -s, s),
           "wv": ((L, d, Hkv * Dh), -s, s),
           "wo": ((L, H * Dh, d), -ATTN_OUT_GAIN / math.sqrt(H * Dh),
                  ATTN_OUT_GAIN / math.sqrt(H * Dh))}
    if a.qkv_bias:
        lay.update(bq=((L, H * Dh), -BIAS_SPREAD, BIAS_SPREAD),
                   bk=((L, Hkv * Dh), -BIAS_SPREAD, BIAS_SPREAD),
                   bv=((L, Hkv * Dh), -BIAS_SPREAD, BIAS_SPREAD))
    f = FFN_OUT_GAIN / math.sqrt(F)
    if E:
        lay.update(router=((L, d, E), -s, s),
                   w_gate=((L, E, d, F), -s, s), w_up=((L, E, d, F), -s, s),
                   w_down=((L, E, F, d), -f, f))
    else:
        lay.update(w_gate=((L, d, F), -s, s), w_up=((L, d, F), -s, s),
                   w_down=((L, F, d), -f, f))
    out.update({f"layers.{k}": v for k, v in lay.items()})
    return out


def _draw(a: Arch, kd) -> Dict:
    key = jax.random.wrap_key_data(kd, impl="threefry2x32")
    w: Dict = {"layers": {}}
    for i, (name, (shape, lo, hi)) in enumerate(shapes(a).items()):
        x = jax.random.uniform(jax.random.fold_in(key, i), shape,
                               jnp.float32, lo, hi)
        if name.startswith("layers."):
            w["layers"][name[len("layers."):]] = x
        else:
            w[name] = x
    return w


def _program_draw(a: Arch, kd) -> Dict:
    return program_params(a, _draw(a, kd))


_make_plain = jax.jit(_draw, static_argnums=0)
_make_program = jax.jit(_program_draw, static_argnums=0)


# The axis each weight is split on when it is drawn across chips: the one a
# tensor-parallel matmul keeps apart, so each chip's share is a whole block of
# its product.  Output features of the q/k/v, gate and up projections (and the
# q/k/v biases), input features of the attention output and down projections,
# the vocabulary of the embedding and head.  Norm weights and the router, under
# a megabyte each, are whole on every chip.
SPLIT = {"embed": 0, "lm_head": 1, "wq": -1, "wk": -1, "wv": -1, "bq": -1,
         "bk": -1, "bv": -1, "wo": -2, "w_gate": -1, "w_up": -1, "w_down": -2}


def placement(tree, devices, whole_experts: bool):
    """``NamedSharding`` of each leaf of ``tree`` (arrays or shapes, keyed by
    the names of :func:`shapes` or of the program's tree) over a 1-D mesh of
    ``devices``: split on its ``SPLIT`` axis where the device count divides
    it, whole otherwise.  A stack of experts (L, E, ., .) is split on its
    expert axis instead when ``whole_experts``: the program's expert-parallel
    engine holds whole experts on each chip, so the served copy then stays
    where it was drawn; the reference takes one expert at a time, so its
    copy keeps the feed-forward axis split and each expert on every chip."""
    n = len(devices)
    mesh = Mesh(np.array(devices), ("chips",))

    def one(path, x):
        axis = SPLIT.get(getattr(path[-1], "key", None))
        if axis is not None and whole_experts and len(x.shape) == 4:
            axis = 1
        spec = [None] * len(x.shape)
        if axis is not None and x.shape[axis] % n == 0:
            spec[axis] = "chips"
        return NamedSharding(mesh, PartitionSpec(*spec))

    return jax.tree_util.tree_map_with_path(one, tree)


def _draw_across(fn, a: Arch, seed: int, devices, whole_experts: bool):
    kd = key_data(seed)
    out = placement(jax.eval_shape(lambda k: fn(a, k), kd), devices,
                    whole_experts)
    return jax.jit(fn, static_argnums=0, out_shardings=out)(a, kd)


def make(a: Arch, seed: int, devices=None) -> Dict:
    """The plain weights of ``seed``: drawn on the default device, or split
    over ``devices`` where they are more than one."""
    if devices is not None and len(devices) > 1:
        return _draw_across(_draw, a, seed, devices, whole_experts=False)
    return _make_plain(a, key_data(seed))


def program_params(a: Arch, w: Dict) -> Dict:
    """The program's parameter tree (``repro.models.lm`` layout) holding the
    plain weights ``w``.  The program's RMSNorm multiplies by
    ``1 + scale``, so its scale is the plain weight less one."""
    lw = w["layers"]
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in lw}
    ffn = {k: lw[k] for k in ("router", "w_gate", "w_up", "w_down") if k in lw}
    p = {"embed": w["embed"],
         "final_norm": {"scale": w["final_norm"] - 1.0},
         "layers": {"ln1": {"scale": lw["attn_norm"] - 1.0},
                    "ln2": {"scale": lw["mlp_norm"] - 1.0},
                    "attn": attn, "ffn": ffn}}
    if not a.tied:
        p["lm_head"] = w["lm_head"]
    return p


def make_program_params(a: Arch, seed: int, devices=None) -> Dict:
    """Draw the weights of ``seed`` and lay them out for the program, in one
    jitted call: the plain copy never exists beside the program's.  On the
    default device, or split over ``devices`` where they are more than one,
    with whole experts on each chip."""
    if devices is not None and len(devices) > 1:
        return _draw_across(_program_draw, a, seed, devices,
                            whole_experts=True)
    return _make_program(a, key_data(seed))


def check_layout(params, expected) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes of
    ``expected`` (the program's own ``init_params`` under ``eval_shape``)."""
    got = jax.tree_util.tree_structure(params)
    want = jax.tree_util.tree_structure(expected)
    if got != want:
        raise ValueError(f"weights do not fit the program's layout:\n"
                         f"  made     {got}\n  program  {want}")
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree_util.tree_leaves(expected)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise ValueError(f"{jax.tree_util.keystr(path)}: made "
                             f"{x.shape} {x.dtype}, program {y.shape} {y.dtype}")
