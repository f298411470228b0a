"""Sharding rules mapping model pytrees onto the production mesh.

Policy (DESIGN.md §5):
  * batch dims           -> ("pod", "data")  (or ("data",) single-pod)
  * weight "FSDP" dim    -> "data"  (ZeRO-3-style; XLA all-gathers per layer)
  * weight tensor-par dim-> "model" (Megatron: heads / d_ff / vocab)
  * KV-cache sequence    -> "model" (kv-head counts < axis size; seq shards evenly)
  * params replicated over "pod" (cross-pod = pure data parallelism; gradient
    all-reduce over "pod" is inserted by XLA)

Every rule is sanitised against divisibility: any dim not divisible by its
assigned axis size falls back to replication on that dim (e.g. vocab 50280 on
a 16-way model axis).
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec


class ShardingFallback(UserWarning):
    """A requested shard assignment was dropped (dim % axis_size != 0) and
    the dim replicated instead.  Warned once per (path, dim, axis) so a
    big pytree doesn't flood logs; recorded in the active
    :class:`ShardingDecision` so cost models price the replication honestly
    instead of assuming the requested TP split happened."""


@dataclass(frozen=True)
class FallbackRecord:
    """One dropped shard assignment: ``path[axis_index]`` of size ``dim``
    was not divisible by ``axis`` (size ``axis_size``) and fell back to
    replication."""
    path: str
    axis_index: int
    dim: int
    axis: str
    axis_size: int


@dataclass
class ShardingDecision:
    """What actually got sharded for one (cfg, policy) pair.

    ``param_specs`` are the sanitised PartitionSpecs; ``fallbacks`` lists
    every dropped assignment.  ``tp_fallback_fraction`` is the share of
    tensor-parallel assignments that silently replicated — the number
    ``hlo_analysis`` feeds into collective/rebuild costing so a policy that
    *requested* tp=8 but got replication is not costed as if it sharded."""
    mode: str
    tp_axis: str
    tp_requested: int
    ep: bool = False
    param_specs: Any = None
    fallbacks: List[FallbackRecord] = field(default_factory=list)

    def _mentions_tp(self, entry) -> bool:
        if entry is None:
            return False
        if isinstance(entry, tuple):
            return self.tp_axis in entry
        return entry == self.tp_axis

    @property
    def tp_fallback_fraction(self) -> float:
        dropped = sum(1 for f in self.fallbacks
                      if self.tp_axis in (f.axis or ""))
        kept = 0
        if self.param_specs is not None:
            for spec in jax.tree_util.tree_leaves(
                    self.param_specs, is_leaf=lambda x: isinstance(x, P)):
                kept += sum(1 for e in spec if self._mentions_tp(e))
        return dropped / max(dropped + kept, 1)

    @property
    def effective_tp(self) -> int:
        """1 when every TP assignment fell back (weights fully replicated);
        the requested degree otherwise — partial fallback is carried via
        ``tp_fallback_fraction`` for Amdahl-style cost adjustments."""
        return 1 if self.tp_fallback_fraction >= 1.0 else self.tp_requested


# warn-once bookkeeping + the decision currently collecting fallbacks;
# module-level because _sanitize is called from deep inside tree_map
_WARNED: set = set()
_ACTIVE_DECISION: Optional[ShardingDecision] = None
_FALLBACK_PATH: str = ""


def _record_fallback(path: str, axis_index: int, dim: int, entry,
                     axis_size: int) -> None:
    axis = "+".join(entry) if isinstance(entry, tuple) else str(entry)
    key = (path, axis_index, axis, dim)
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(
            f"sharding fallback: {path or '<anon>'}[{axis_index}] dim={dim} "
            f"not divisible by axis {axis!r} (size {axis_size}); replicating",
            ShardingFallback, stacklevel=3)
    if _ACTIVE_DECISION is not None:
        _ACTIVE_DECISION.fallbacks.append(
            FallbackRecord(path, axis_index, dim, axis, axis_size))


def sharding_decision(cfg: ModelConfig, pol: "ShardingPolicy",
                      params_sds) -> ShardingDecision:
    """Compute param specs while recording every divisibility fallback."""
    global _ACTIVE_DECISION
    d = ShardingDecision(mode=pol.mode, tp_axis=pol.tp_axis,
                         tp_requested=pol.tp_size, ep=pol.ep)
    _ACTIVE_DECISION = d
    try:
        d.param_specs = param_pspecs(cfg, pol, params_sds)
    finally:
        _ACTIVE_DECISION = None
    return d


@dataclass(frozen=True)
class ShardingPolicy:
    mesh: Mesh
    mode: str = "tp"                 # "tp": Megatron TP × FSDP; "fsdp": pure ZeRO-3/DP
    tp_axis: str = "model"
    fsdp_axis: Optional[str] = "data"
    batch_axes: Tuple[str, ...] = ("data",)
    # decode-2D-TP (§Perf): replicate the (tiny) decode batch so the data
    # axis is free for weight-row sharding with partial-sum matmuls instead
    # of per-step weight all-gathers
    replicate_batch: bool = False
    # expert parallelism: shard the MoE expert axis on tp_axis (dense-mix
    # semantics, gate-weighted psum combine) instead of slicing d_ff —
    # serving-time Mixtral routing through kernels/moe_gmm per shard
    ep: bool = False

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]

    @property
    def batch_axes_pref(self) -> Tuple[str, ...]:
        """Preference order for batch sharding; fsdp mode also uses the model
        axis for pure data parallelism."""
        if self.mode == "fsdp":
            return (*self.batch_axes, self.tp_axis)
        return self.batch_axes

    @property
    def batch_size_divisor(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))


def _tp_compatible(cfg: ModelConfig, tp: int) -> bool:
    """Megatron-style head sharding needs q-head counts divisible by tp."""
    if cfg.family == "ssm":
        return cfg.ssm.n_heads(cfg.d_model) % tp == 0
    if cfg.n_heads % tp != 0:
        return False
    if cfg.family == "hybrid" and cfg.ssm is not None:
        if cfg.ssm.n_heads(cfg.d_model) % tp != 0:
            return False
    return True


def make_policy(mesh: Mesh, cfg: Optional[ModelConfig] = None,
                ep: Optional[bool] = None) -> ShardingPolicy:
    axes = tuple(mesh.axis_names)
    batch_axes = ("pod", "data") if "pod" in axes else ("data",)
    mode = "tp"
    tp = mesh.shape["model"]
    if cfg is not None and not _tp_compatible(cfg, tp):
        mode = "fsdp"
    if ep is None:
        # expert parallelism by default whenever the expert axis divides:
        # the MoE FFN dominates Mixtral FLOPs and shards losslessly on the
        # expert axis even when d_ff/head counts would not
        ep = bool(cfg is not None and cfg.family == "moe" and tp > 1
                  and cfg.n_experts % tp == 0)
    return ShardingPolicy(mesh, mode=mode, batch_axes=batch_axes, ep=ep)


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        return int(np.prod([mesh.shape[a] for a in entry]))
    return mesh.shape[entry]


def _sanitize(mesh: Mesh, shape: Tuple[int, ...], spec: Tuple,
              path: str = "") -> P:
    """Drop axis assignments whose dim isn't divisible by the axis size.
    Each drop warns once (:class:`ShardingFallback`) and is recorded in the
    active :class:`ShardingDecision`, so replicated dims are costed
    honestly downstream instead of assumed sharded."""
    out = []
    for i, (dim, entry) in enumerate(zip(shape, spec)):
        if entry is not None and dim % _axis_size(mesh, entry) != 0:
            _record_fallback(path, i, dim, entry, _axis_size(mesh, entry))
            entry = None
        out.append(entry)
    return P(*out)


def _pad(shape: Tuple[int, ...], trailing: Tuple) -> Tuple:
    """Prepend None for stacked leading dims (scan stacking)."""
    return tuple([None] * (len(shape) - len(trailing))) + tuple(trailing)


# --------------------------------------------------------------------------- #
# parameter specs
# --------------------------------------------------------------------------- #
def _param_rule(cfg: ModelConfig, pol: ShardingPolicy, path: Tuple[str, ...],
                shape: Tuple[int, ...]) -> Tuple:
    tp, fs = pol.tp_axis, pol.fsdp_axis
    name = path[-1]
    in_moe_ffn = (cfg.family == "moe" and "ffn" in path)

    if name == "embed":
        return (tp, fs)
    if name == "lm_head":
        return (fs, tp)
    if name == "enc_pos":
        return (None, None)
    if name in ("scale", "A_log", "D", "dt_bias"):
        return (None,)
    if name == "norm_scale":
        return (tp,)
    if name in ("bq", "bk", "bv", "conv_b"):
        return (tp,)
    if name == "conv_w":
        return (None, tp)
    if name == "router":
        return (fs, None)
    if in_moe_ffn and name in ("w_gate", "w_up"):
        # EP shards the expert axis (whole experts per device, moe_gmm runs
        # shard-local); TP slices every expert's d_ff instead
        return (tp, fs, None) if pol.ep else (None, fs, tp)
    if in_moe_ffn and name == "w_down":
        return (tp, None, fs) if pol.ep else (None, tp, fs)
    if name in ("wq", "wk", "wv", "w_gate", "w_up"):
        return (fs, tp)
    if name in ("wo", "w_down"):
        return (tp, fs)
    if name in ("wq_a", "wkv_a"):
        return (fs, None)
    if name in ("wq_b", "wk_b", "wv_b"):
        return (None, tp)
    if name == "w":  # in_proj / out_proj inner linears (mamba blocks)
        if "out_proj" in path:
            return (tp, fs)
        return (fs, tp)
    if name == "b":
        return (tp,)
    return tuple([None] * len(shape))


def _path_names(kp) -> Tuple[str, ...]:
    names = []
    for k in kp:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "name"):
            names.append(str(k.name))
        else:
            names.append(str(k))
    return tuple(names)


def param_pspecs(cfg: ModelConfig, pol: ShardingPolicy, params_sds) -> Any:
    def one(kp, leaf):
        path = _path_names(kp)
        rule = _param_rule(cfg, pol, path, leaf.shape)
        return _sanitize(pol.mesh, leaf.shape, _pad(leaf.shape, rule),
                         path=".".join(path))

    return jax.tree_util.tree_map_with_path(one, params_sds)


def opt_pspecs(cfg: ModelConfig, pol: ShardingPolicy, opt_sds) -> Any:
    """m/v mirror param shardings; step counter replicated."""
    def one(kp, leaf):
        path = _path_names(kp)
        if path and path[0] == "step":
            return P()
        # strip leading "m"/"v" so the param rules see the real path
        rule_path = path[1:] if path and path[0] in ("m", "v") else path
        rule = _param_rule(cfg, pol, rule_path, leaf.shape)
        return _sanitize(pol.mesh, leaf.shape, _pad(leaf.shape, rule),
                         path=".".join(path))

    return jax.tree_util.tree_map_with_path(one, opt_sds)


# --------------------------------------------------------------------------- #
# batch / cache / output specs
# --------------------------------------------------------------------------- #
def _batch_entry(pol: ShardingPolicy, B: int, ignore_replicate: bool = False):
    """Longest prefix of the batch-axis preference list that divides B."""
    if pol.replicate_batch and not ignore_replicate:
        return None
    pref = pol.batch_axes_pref
    for k in range(len(pref), 0, -1):
        cand = pref[:k]
        if B % int(np.prod([pol.mesh.shape[a] for a in cand])) == 0:
            return cand if len(cand) > 1 else cand[0]
    return None


def activation_shard_flags(pol: ShardingPolicy, B: int, S: int) -> Dict[str, Any]:
    """Value for flags['act_shard']: hidden-state constraint per cell.

    Hidden states (B, S, D) -> P(batch, model, None): batch over the data
    axes, sequence over the model axis (Megatron-style sequence parallelism —
    residual-stream tensors and remat-saved scan carries shrink by tp_size;
    XLA inserts the all-gather at each matmul entry / reduce-scatter at exit).
    """
    b = _batch_entry(pol, B)
    bsz = 1 if b is None else _axis_size(pol.mesh, b)
    b_axes = (b,) if isinstance(b, str) else (b or ())
    seq = None
    if (S > 1 and S % pol.tp_size == 0 and pol.tp_axis not in b_axes):
        seq = pol.tp_axis
    return {"batch": b, "batch_size": bsz,
            "seq": seq, "seq_size": pol.tp_size if seq else 1}


def batch_pspecs(cfg: ModelConfig, pol: ShardingPolicy, batch_sds) -> Any:
    def one(kp, leaf):
        b = _batch_entry(pol, leaf.shape[0])
        rest = [None] * (len(leaf.shape) - 1)
        return _sanitize(pol.mesh, leaf.shape, (b, *rest))

    return jax.tree_util.tree_map_with_path(one, batch_sds)


def cache_pspecs(cfg: ModelConfig, pol: ShardingPolicy, cache_sds) -> Any:
    """KV caches: (stack..., B, S, H, D) -> seq sharded on tp; ssm states:
    heads sharded on tp. Batch on batch axes when divisible.

    Under decode-2D-TP (replicate_batch) the cache KEEPS its batch sharding:
    attention then stays shard-local over batch slices while hidden states
    replicate — weight gathers turn into small activation collectives."""
    tp = pol.tp_axis

    def one(kp, leaf):
        path = _path_names(kp)
        name = path[-1]
        shape = leaf.shape
        nstack = 2 if "groups" in path and name in ("conv", "ssm") else 1
        b = _batch_entry(pol, shape[nstack], ignore_replicate=True)
        if name in ("xk", "xv"):                      # whisper cross KV (F=1500)
            spec = (None, b, None, None, None)
        elif name in ("k", "v") or name.endswith("_k") or name.endswith("_v"):
            spec = (None, b, tp, None, None)
        elif name == "ckv":                           # MLA latent
            spec = (None, b, tp, None)
        elif name == "pos" or name.endswith("_pos"):
            spec = (None, b, tp)
        elif name == "conv":
            spec = tuple([None] * nstack) + (b, None, tp)
        elif name == "ssm":
            spec = tuple([None] * nstack) + (b, tp, None, None)
        else:
            spec = tuple([None] * len(shape))
        return _sanitize(pol.mesh, shape, spec, path=".".join(path))

    return jax.tree_util.tree_map_with_path(one, cache_sds)


def paged_cache_pspecs(cfg: ModelConfig, pol: ShardingPolicy,
                       cache_sds) -> Any:
    """Paged KV pool: (L, n_pages, H, page_size, D) shards KV **heads** on
    the tp axis — page indices are request-local and must stay addressable
    from every shard, so the page axis replicates and the head axis (which
    TP attention already splits) carries the partition.  MLA's latent pool
    has no head axis and replicates."""
    tp = pol.tp_axis

    def one(kp, leaf):
        path = _path_names(kp)
        name = path[-1]
        if name in ("kp", "vp"):
            spec = (None, None, tp, None, None)
        else:                               # ckvp + anything unforeseen
            spec = tuple([None] * len(leaf.shape))
        return _sanitize(pol.mesh, leaf.shape, spec, path=".".join(path))

    return jax.tree_util.tree_map_with_path(one, cache_sds)


# --------------------------------------------------------------------------- #
# full in/out shardings per step kind
# --------------------------------------------------------------------------- #
def _ns(mesh: Mesh, tree):
    return jax.tree.map(lambda p: NamedSharding(mesh, p), tree,
                        is_leaf=lambda x: isinstance(x, P))


def step_shardings(cfg: ModelConfig, shape: ShapeSpec, pol: ShardingPolicy,
                   specs: Dict[str, Any]):
    """Returns (in_shardings, out_shardings) trees matching step signatures."""
    mesh = pol.mesh
    p_params = param_pspecs(cfg, pol, specs["params"])
    if shape.kind == "train":
        p_opt = opt_pspecs(cfg, pol, specs["opt_state"])
        p_batch = batch_pspecs(cfg, pol, specs["batch"])
        in_sh = (_ns(mesh, p_params), _ns(mesh, p_opt), _ns(mesh, p_batch))
        out_sh = (NamedSharding(mesh, P()), _ns(mesh, p_params), _ns(mesh, p_opt))
        return in_sh, out_sh
    if shape.kind == "prefill":
        p_batch = batch_pspecs(cfg, pol, specs["batch"])
        b = _batch_entry(pol, shape.global_batch)
        out = NamedSharding(mesh, _sanitize(
            mesh, (shape.global_batch, cfg.vocab_size), (b, pol.tp_axis)))
        return (_ns(mesh, p_params), _ns(mesh, p_batch)), out
    # decode
    p_cache = cache_pspecs(cfg, pol, specs["cache"])
    b = _batch_entry(pol, shape.global_batch)
    tok_sh = NamedSharding(mesh, _sanitize(mesh, (shape.global_batch, 1), (b, None)))
    pos_sh = NamedSharding(mesh, _sanitize(mesh, (shape.global_batch,), (b,)))
    in_sh = (_ns(mesh, p_params), _ns(mesh, p_cache), tok_sh, pos_sh)
    out_tok = NamedSharding(mesh, _sanitize(mesh, (shape.global_batch,), (b,)))
    out_sh = (out_tok, _ns(mesh, p_cache))
    return in_sh, out_sh
