"""Unified language model covering all 10 assigned architectures.

Layer-stack patterns (all compile-time static):
  * uniform   — dense / moe / vlm / ssm: ``lax.scan`` over L stacked layers
  * pairs     — gemma2: scan over L/2 (local, global) pairs
  * groups    — zamba2: scan over groups of (attn_every-1 mamba + shared attn)
  * encdec    — whisper: encoder scan + decoder scan with cross-attention

``forward`` is used by train/prefill (full sequence); ``decode_step`` advances
one token against a KV/SSM cache.  ``init_cache`` defines the cache pytree —
``jax.eval_shape`` over it yields the dry-run ShapeDtypeStructs.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import flags
from repro.models.layers import (
    attention_fwd,
    init_attention,
    init_linear,
    init_mla,
    init_moe,
    init_rmsnorm,
    init_swiglu,
    mla_fwd,
    moe_dense_mix,
    moe_dispatch,
    paged_attention_fwd,
    paged_mla_fwd,
    rmsnorm,
    shard_hidden,
    softcap,
    swiglu,
)
from repro.models.ssd import init_mamba2, mamba2_fwd

Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
# per-layer init / fwd
# --------------------------------------------------------------------------- #
def _init_decoder_layer(key, cfg: ModelConfig, cross: bool = False) -> Params:
    ks = jax.random.split(key, 5)
    p: Params = {"ln1": init_rmsnorm(cfg.d_model), "ln2": init_rmsnorm(cfg.d_model)}
    if cfg.mla is not None:
        p["attn"] = init_mla(ks[0], cfg)
    else:
        p["attn"] = init_attention(ks[0], cfg)
    if cfg.family == "moe":
        p["ffn"] = init_moe(ks[1], cfg)
    else:
        p["ffn"] = init_swiglu(ks[1], cfg.d_model, cfg.d_ff)
    if cross:
        p["ln_x"] = init_rmsnorm(cfg.d_model)
        p["xattn"] = init_attention(ks[2], cfg)
    return p


def _ffn_fwd(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.family == "moe":
        ep = flags.get_flag("ep_shard")
        if ep is not None:
            # expert-parallel shard_map path (trace-time flag set by sharded
            # engines): dense-mix semantics, token-identical to the baseline
            from repro.distributed.expert_parallel import ep_moe_mix
            return ep_moe_mix(p, cfg, x, ep["mesh"], ep.get("axis", "model"))
        impl = flags.get_flag("moe_impl")
        return (moe_dispatch if impl == "dispatch" else moe_dense_mix)(p, cfg, x)
    return swiglu(p, x)


def _decoder_layer_fwd(p: Params, cfg: ModelConfig, x: jax.Array,
                       positions: jax.Array, window: Optional[int],
                       cache=None, enc_out=None, xattn_cache=None):
    """Pre-norm decoder layer. Returns (x, new_cache, new_xattn_cache)."""
    q_chunk = flags.get_flag("q_chunk")
    h = rmsnorm(x, p["ln1"]["scale"], cfg.norm_eps)
    if cfg.mla is not None:
        if cache is None:
            attn_out, new_cache = mla_fwd(p["attn"], cfg, h, positions,
                                          q_chunk=q_chunk)
        else:
            attn_out, new_cache = mla_fwd(p["attn"], cfg, h, positions,
                                          kv_cache=cache[0], cache_positions=cache[1],
                                          q_chunk=q_chunk)
    else:
        if cache is None:
            attn_out, new_cache = attention_fwd(p["attn"], cfg, h, positions, window,
                                                q_chunk=q_chunk)
        else:
            attn_out, new_cache = attention_fwd(
                p["attn"], cfg, h, positions, window,
                kv_cache=(cache[0], cache[1]), cache_positions=cache[2],
                q_chunk=q_chunk)
    x = x + attn_out
    new_xattn = None
    if enc_out is not None or xattn_cache is not None:
        h = rmsnorm(x, p["ln_x"]["scale"], cfg.norm_eps)
        if xattn_cache is not None:
            xk, xv = xattn_cache
        else:
            B, F, _ = enc_out.shape
            xk = (enc_out @ p["xattn"]["wk"].astype(x.dtype)).reshape(
                B, F, cfg.n_kv_heads, cfg.d_head)
            xv = (enc_out @ p["xattn"]["wv"].astype(x.dtype)).reshape(
                B, F, cfg.n_kv_heads, cfg.d_head)
        xout, _ = attention_fwd(p["xattn"], cfg, h, positions, None,
                                xattn_kv=(xk, xv), causal=False, q_chunk=q_chunk)
        x = x + xout
        new_xattn = (xk, xv)
    h = rmsnorm(x, p["ln2"]["scale"], cfg.norm_eps)
    x = x + _ffn_fwd(p["ffn"], cfg, h)
    return shard_hidden(x), new_cache, new_xattn


def _init_mamba_layer(key, cfg: ModelConfig) -> Params:
    return {"ln": init_rmsnorm(cfg.d_model), "mixer": init_mamba2(key, cfg)}


def _mamba_layer_fwd(p: Params, cfg: ModelConfig, x: jax.Array, state=None):
    h = rmsnorm(x, p["ln"]["scale"], cfg.norm_eps)
    out, new_state = mamba2_fwd(p["mixer"], cfg, h, state)
    return shard_hidden(x + out), new_state


# --------------------------------------------------------------------------- #
# model init
# --------------------------------------------------------------------------- #
def _stack_init(init_fn, key, n: int) -> Params:
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    ks = jax.random.split(key, 8)
    scale = 1.0 / math.sqrt(cfg.d_model)
    p: Params = {
        "embed": jax.random.uniform(ks[0], (cfg.vocab_size, cfg.d_model),
                                    jnp.float32, -scale, scale),
        "final_norm": init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.uniform(ks[1], (cfg.d_model, cfg.vocab_size),
                                          jnp.float32, -scale, scale)

    if cfg.family == "ssm":
        p["layers"] = _stack_init(lambda k: _init_mamba_layer(k, cfg), ks[2], cfg.n_layers)
    elif cfg.family == "hybrid":
        G = cfg.n_layers // cfg.attn_every
        per_group = cfg.attn_every - 1
        trailing = cfg.n_layers - G * cfg.attn_every
        p["mamba_groups"] = jax.vmap(
            lambda k: _stack_init(lambda kk: _init_mamba_layer(kk, cfg), k, per_group)
        )(jax.random.split(ks[2], G))
        if trailing:
            p["mamba_tail"] = _stack_init(lambda k: _init_mamba_layer(k, cfg),
                                          ks[3], trailing)
        p["shared_attn"] = _init_decoder_layer(ks[4], cfg)
    elif cfg.local_global_every == 2:
        L2 = cfg.n_layers // 2
        p["layer_pairs"] = jax.vmap(
            lambda k: _stack_init(lambda kk: _init_decoder_layer(kk, cfg), k, 2)
        )(jax.random.split(ks[2], L2))
    elif cfg.is_encoder_decoder:
        p["enc_pos"] = jax.random.uniform(ks[5], (cfg.n_frames, cfg.d_model),
                                          jnp.float32, -scale, scale)
        p["enc_layers"] = _stack_init(lambda k: _init_decoder_layer(k, cfg),
                                      ks[2], cfg.n_encoder_layers)
        p["enc_norm"] = init_rmsnorm(cfg.d_model)
        p["layers"] = _stack_init(lambda k: _init_decoder_layer(k, cfg, cross=True),
                                  ks[3], cfg.n_layers)
    else:
        p["layers"] = _stack_init(lambda k: _init_decoder_layer(k, cfg),
                                  ks[2], cfg.n_layers)
    return p


# parameter leaves, by their key, that every forward reads only as
# ``w.astype(x.dtype)`` with ``x`` in the compute dtype: matrices, biases and
# the embedding (its rows and, tied, the head).  Norm scales and SSM
# parameters are read in float32 and are not among them.
_COMPUTE_DTYPE_LEAVES = frozenset({
    "embed", "lm_head",
    "wq", "wk", "wv", "wo", "bq", "bk", "bv",
    "wq_a", "wq_b", "wkv_a", "wk_b", "wv_b",
    "w_gate", "w_up", "w_down", "router"})


def compute_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def serving_params(cfg: ModelConfig, params: Params) -> Params:
    """``params`` as a served step reads them: each leaf of
    ``_COMPUTE_DTYPE_LEAVES`` in the compute dtype, every other leaf as given.

    Rounding once here gives the operands that each step would otherwise
    round again from float32, so the forward's results are bit-identical
    and no step program converts a weight.  A leaf already in its dtype is
    passed through, not copied; shapes (``jax.ShapeDtypeStruct``) map to the
    shapes of the cast tree.
    """
    dt = jnp.dtype(compute_dtype(cfg))

    def one(path, x):
        if (getattr(path[-1], "key", None) not in _COMPUTE_DTYPE_LEAVES
                or x.dtype == dt):
            return x
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, dt, sharding=x.sharding)
        return x.astype(dt)

    return jax.tree_util.tree_map_with_path(one, params)


# --------------------------------------------------------------------------- #
# remat helper
# --------------------------------------------------------------------------- #
def _maybe_remat(fn):
    pol = flags.get_flag("remat")
    if pol == "none":
        return fn
    if pol == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# --------------------------------------------------------------------------- #
# forward (full sequence: train / prefill)
# --------------------------------------------------------------------------- #
def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            frames: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence forward. tokens: (B, S) int32 → logits (B, S, V)."""
    B, S = tokens.shape
    dtype = compute_dtype(cfg)
    x = params["embed"][tokens].astype(dtype)
    if cfg.local_global_every:          # gemma-style embedding normalizer
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    x = shard_hidden(x)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    if cfg.family == "ssm":
        def body(h, lp):
            h, _ = _mamba_layer_fwd(lp, cfg, h)
            return h, None
        x, _ = jax.lax.scan(_maybe_remat(body), x, params["layers"])

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def group_body(h, glp):
            def inner(h2, lp):
                h2, _ = _mamba_layer_fwd(lp, cfg, h2)
                return h2, None
            h, _ = jax.lax.scan(inner, h, glp)
            h, _, _ = _decoder_layer_fwd(shared, cfg, h, positions, None)
            return h, None
        x, _ = jax.lax.scan(_maybe_remat(group_body), x, params["mamba_groups"])
        if "mamba_tail" in params:
            def tail(h, lp):
                h, _ = _mamba_layer_fwd(lp, cfg, h)
                return h, None
            x, _ = jax.lax.scan(_maybe_remat(tail), x, params["mamba_tail"])

    elif cfg.local_global_every == 2:
        def pair_body(h, lp2):
            loc = jax.tree.map(lambda t: t[0], lp2)
            glob = jax.tree.map(lambda t: t[1], lp2)
            h, _, _ = _decoder_layer_fwd(loc, cfg, h, positions, cfg.sliding_window)
            h, _, _ = _decoder_layer_fwd(glob, cfg, h, positions, None)
            return h, None
        x, _ = jax.lax.scan(_maybe_remat(pair_body), x, params["layer_pairs"])

    elif cfg.is_encoder_decoder:
        assert frames is not None, "whisper forward requires frame embeddings"
        enc = frames.astype(dtype) + params["enc_pos"][None].astype(dtype)
        fpos = jnp.broadcast_to(
            jnp.arange(enc.shape[1], dtype=jnp.int32)[None], enc.shape[:2])

        def enc_body(h, lp):
            hh = rmsnorm(h, lp["ln1"]["scale"], cfg.norm_eps)
            o, _ = attention_fwd(lp["attn"], cfg, hh, fpos, None, causal=False,
                                 q_chunk=flags.get_flag("q_chunk"))
            h = h + o
            hh = rmsnorm(h, lp["ln2"]["scale"], cfg.norm_eps)
            return h + swiglu(lp["ffn"], hh), None
        enc, _ = jax.lax.scan(_maybe_remat(enc_body), enc, params["enc_layers"])
        enc = rmsnorm(enc, params["enc_norm"]["scale"], cfg.norm_eps)

        def dec_body(h, lp):
            h, _, _ = _decoder_layer_fwd(lp, cfg, h, positions, None, enc_out=enc)
            return h, None
        x, _ = jax.lax.scan(_maybe_remat(dec_body), x, params["layers"])

    else:
        window = cfg.sliding_window

        def body(h, lp):
            h, _, _ = _decoder_layer_fwd(lp, cfg, h, positions, window)
            return h, None
        x, _ = jax.lax.scan(_maybe_remat(body), x, params["layers"])

    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head.astype(x.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits


# --------------------------------------------------------------------------- #
# cache
# --------------------------------------------------------------------------- #
def _kv_zeros(cfg: ModelConfig, n: int, B: int, S: int, dtype) -> Dict[str, jax.Array]:
    return {
        "k": jnp.zeros((n, B, S, cfg.n_kv_heads, cfg.d_head), dtype),
        "v": jnp.zeros((n, B, S, cfg.n_kv_heads, cfg.d_head), dtype),
        "pos": jnp.full((n, B, S), -1, jnp.int32),
    }


def _ssm_zeros(cfg: ModelConfig, shape_prefix, B: int, dtype) -> Dict[str, jax.Array]:
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    conv_dim = di + 2 * s.n_groups * s.d_state
    nh = s.n_heads(cfg.d_model)
    return {
        "conv": jnp.zeros((*shape_prefix, B, s.d_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((*shape_prefix, B, nh, s.head_dim, s.d_state), dtype),
    }


def cache_seq_len(cfg: ModelConfig, seq_len: int) -> int:
    """Physical KV buffer length (rolling buffer for pure-SWA archs)."""
    if cfg.sliding_window is not None and cfg.local_global_every == 0:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def init_cache(cfg: ModelConfig, B: int, seq_len: int, dtype=jnp.bfloat16) -> Params:
    """Zero-filled cache pytree for decoding up to ``seq_len`` positions."""
    S = cache_seq_len(cfg, seq_len)
    if cfg.family == "ssm":
        return _ssm_zeros(cfg, (cfg.n_layers,), B, dtype)
    if cfg.family == "hybrid":
        G = cfg.n_layers // cfg.attn_every
        per_group = cfg.attn_every - 1
        trailing = cfg.n_layers - G * cfg.attn_every
        c = {"groups": _ssm_zeros(cfg, (G, per_group), B, dtype)}
        c.update({f"attn_{k}": v for k, v in
                  _kv_zeros(cfg, G, B, seq_len, dtype).items()})
        if trailing:
            c["tail"] = _ssm_zeros(cfg, (trailing,), B, dtype)
        return c
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "ckv": jnp.zeros((cfg.n_layers, B, S, m.kv_lora_rank + m.qk_rope_head_dim),
                             dtype),
            "pos": jnp.full((cfg.n_layers, B, S), -1, jnp.int32),
        }
    if cfg.local_global_every == 2:
        L2 = cfg.n_layers // 2
        Sl = min(cfg.sliding_window, seq_len)
        c = {f"loc_{k}": v for k, v in _kv_zeros(cfg, L2, B, Sl, dtype).items()}
        c.update({f"glob_{k}": v for k, v in _kv_zeros(cfg, L2, B, seq_len, dtype).items()})
        return c
    if cfg.is_encoder_decoder:
        c = _kv_zeros(cfg, cfg.n_layers, B, S, dtype)
        c["xk"] = jnp.zeros((cfg.n_layers, B, cfg.n_frames, cfg.n_kv_heads, cfg.d_head),
                            dtype)
        c["xv"] = jnp.zeros_like(c["xk"])
        return c
    return _kv_zeros(cfg, cfg.n_layers, B, S, dtype)


# --------------------------------------------------------------------------- #
# decode step
# --------------------------------------------------------------------------- #
def decode_step(params: Params, cfg: ModelConfig, cache: Params,
                tokens: jax.Array, positions: jax.Array
                ) -> Tuple[jax.Array, Params]:
    """One decoding step. tokens: (B, 1) int32; positions: (B,) int32.

    Returns (logits (B, 1, V), updated cache).
    """
    return step_with_cache(params, cfg, cache, tokens, positions[:, None])


def prefill_step(params: Params, cfg: ModelConfig, cache: Params,
                 tokens: jax.Array, positions: jax.Array
                 ) -> Tuple[jax.Array, Params]:
    """Chunked prefill: advance C tokens against the cache in ONE dispatch.

    tokens: (B, C) int32; positions: (B, C) int32, contiguous per row
    (cache writes land at positions[:, 0] .. positions[:, 0] + C - 1; a
    chunk must not wrap a rolling SWA buffer — the engine picks chunk sizes
    that divide the buffer length).  Returns (logits (B, C, V), new cache).
    """
    return step_with_cache(params, cfg, cache, tokens, positions)


def step_with_cache(params: Params, cfg: ModelConfig, cache: Params,
                    tokens: jax.Array, pos2: jax.Array
                    ) -> Tuple[jax.Array, Params]:
    """Cache-backed forward over a token chunk. tokens/pos2: (B, C) int32."""
    B = tokens.shape[0]
    dtype = compute_dtype(cfg)
    x = params["embed"][tokens].astype(dtype)
    if cfg.local_global_every:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)

    if cfg.family == "ssm":
        def body(h, xs):
            lp, conv, ssm = xs
            h, (c2, s2) = _mamba_layer_fwd(lp, cfg, h, state=(conv, ssm))
            return h, (c2, s2)
        x, (c2, s2) = jax.lax.scan(body, x, (params["layers"],
                                             cache["conv"], cache["ssm"]))
        new_cache = {"conv": c2, "ssm": s2}

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        window = None

        def group_body(h, xs):
            glp, conv, ssm, kc, vc, pc = xs

            def inner(h2, ys):
                lp, c1, s1 = ys
                h2, (c2, s2) = _mamba_layer_fwd(lp, cfg, h2, state=(c1, s1))
                return h2, (c2, s2)
            h, (c2, s2) = jax.lax.scan(inner, h, (glp, conv, ssm))
            h, kv, _ = _decoder_layer_fwd(shared, cfg, h, pos2, window,
                                          cache=(kc, vc, pc))
            return h, (c2, s2, *kv)
        x, (c2, s2, K, V, P) = jax.lax.scan(
            group_body, x,
            (params["mamba_groups"], cache["groups"]["conv"], cache["groups"]["ssm"],
             cache["attn_k"], cache["attn_v"], cache["attn_pos"]))
        new_cache = {"groups": {"conv": c2, "ssm": s2},
                     "attn_k": K, "attn_v": V, "attn_pos": P}
        if "mamba_tail" in params:
            def tail(h, xs):
                lp, c1, s1 = xs
                h, (c2t, s2t) = _mamba_layer_fwd(lp, cfg, h, state=(c1, s1))
                return h, (c2t, s2t)
            x, (ct, st) = jax.lax.scan(tail, x, (params["mamba_tail"],
                                                 cache["tail"]["conv"],
                                                 cache["tail"]["ssm"]))
            new_cache["tail"] = {"conv": ct, "ssm": st}

    elif cfg.mla is not None:
        def body(h, xs):
            lp, ckv, pc = xs
            h, nc, _ = _decoder_layer_fwd(lp, cfg, h, pos2, None, cache=(ckv, pc))
            return h, nc
        x, (CKV, P) = jax.lax.scan(body, x, (params["layers"],
                                             cache["ckv"], cache["pos"]))
        new_cache = {"ckv": CKV, "pos": P}

    elif cfg.local_global_every == 2:
        def pair_body(h, xs):
            lp2, kl, vl, pl, kg, vg, pg = xs
            loc = jax.tree.map(lambda t: t[0], lp2)
            glob = jax.tree.map(lambda t: t[1], lp2)
            h, kvl, _ = _decoder_layer_fwd(loc, cfg, h, pos2, cfg.sliding_window,
                                           cache=(kl, vl, pl))
            h, kvg, _ = _decoder_layer_fwd(glob, cfg, h, pos2, None,
                                           cache=(kg, vg, pg))
            return h, (*kvl, *kvg)
        x, (KL, VL, PL, KG, VG, PG) = jax.lax.scan(
            pair_body, x,
            (params["layer_pairs"], cache["loc_k"], cache["loc_v"], cache["loc_pos"],
             cache["glob_k"], cache["glob_v"], cache["glob_pos"]))
        new_cache = {"loc_k": KL, "loc_v": VL, "loc_pos": PL,
                     "glob_k": KG, "glob_v": VG, "glob_pos": PG}

    elif cfg.is_encoder_decoder:
        def body(h, xs):
            lp, kc, vc, pc, xk, xv = xs
            h, kv, _ = _decoder_layer_fwd(lp, cfg, h, pos2, None,
                                          cache=(kc, vc, pc), xattn_cache=(xk, xv))
            return h, kv
        x, (K, V, P) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], cache["pos"],
                      cache["xk"], cache["xv"]))
        new_cache = {"k": K, "v": V, "pos": P,
                     "xk": cache["xk"], "xv": cache["xv"]}

    else:
        window = cfg.sliding_window

        def body(h, xs):
            lp, kc, vc, pc = xs
            h, kv, _ = _decoder_layer_fwd(lp, cfg, h, pos2, window,
                                          cache=(kc, vc, pc))
            return h, kv
        x, (K, V, P) = jax.lax.scan(body, x, (params["layers"],
                                              cache["k"], cache["v"], cache["pos"]))
        new_cache = {"k": K, "v": V, "pos": P}

    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head.astype(x.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits, new_cache


# --------------------------------------------------------------------------- #
# pipeline stages (layer-granular slicing for pp replicas)
# --------------------------------------------------------------------------- #
def stage_sliceable(cfg: ModelConfig) -> bool:
    """Families whose params hold ONE homogeneous stacked ``layers`` pytree
    and whose contiguous cache stacks every leaf on a leading layer axis, so
    a pipeline stage is a pure ``[lo:hi]`` slice: dense/moe (incl. pure
    SWA), MLA, vlm, and plain SSM.  Hybrid recurrent groups, encoder-decoder
    xattn, and gemma-style local/global pairs interleave heterogeneous
    blocks and stay at pp=1."""
    return (cfg.family != "hybrid"
            and not cfg.is_encoder_decoder
            and cfg.local_global_every == 0)


def slice_stage_params(cfg: ModelConfig, params: Params, lo: int, hi: int,
                       first: bool, last: bool) -> Params:
    """Parameter slice for one pipeline stage over layers ``[lo, hi)``.

    The first stage carries the embedding table (token lookup); the last
    carries the final norm and LM head — which is the embedding again for
    tied-weight configs, so those replicate the table on both end stages.
    """
    sp: Params = {"layers": jax.tree.map(lambda t: t[lo:hi], params["layers"])}
    if first or (last and cfg.tie_embeddings):
        sp["embed"] = params["embed"]
    if last:
        sp["final_norm"] = params["final_norm"]
        if not cfg.tie_embeddings:
            sp["lm_head"] = params["lm_head"]
    return sp


def slice_stage_cache(cache: Params, lo: int, hi: int) -> Params:
    """Cache slice for layers ``[lo, hi)`` — every contiguous-cache leaf of a
    stage-sliceable family has a leading layer axis."""
    return jax.tree.map(lambda t: t[lo:hi], cache)


def concat_stage_states(parts: Sequence[Params]) -> Params:
    """Reassemble per-stage ``extract_slot`` states (host NumPy, leading
    layer axis) into the full per-layer wire format — byte-identical to a
    single-engine extract, so a pipelined export installs anywhere."""
    return jax.tree.map(lambda *ls: np.concatenate(ls, axis=0), *parts)


def stage_step(params: Params, cfg: ModelConfig, cache: Params,
               x: jax.Array, pos2: jax.Array, *, first: bool, last: bool
               ) -> Tuple[jax.Array, Params]:
    """Cache-backed forward over ONE pipeline stage's layer slice.

    ``x`` is int32 tokens (B, C) on the first stage and the previous stage's
    hidden state (B, C, D) otherwise; returns logits (B, C, V) on the last
    stage and the hidden state to hand off otherwise.  Composing the stages
    in order reproduces :func:`step_with_cache` exactly — same scans, same
    reduction order — which is what makes pp parity bit-exact in float32.
    """
    dtype = compute_dtype(cfg)
    if first:
        x = params["embed"][x].astype(dtype)
        if cfg.local_global_every:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)

    if cfg.family == "ssm":
        def body(h, xs):
            lp, conv, ssm = xs
            h, (c2, s2) = _mamba_layer_fwd(lp, cfg, h, state=(conv, ssm))
            return h, (c2, s2)
        x, (c2, s2) = jax.lax.scan(body, x, (params["layers"],
                                             cache["conv"], cache["ssm"]))
        new_cache = {"conv": c2, "ssm": s2}
    elif cfg.mla is not None:
        def body(h, xs):
            lp, ckv, pc = xs
            h, nc, _ = _decoder_layer_fwd(lp, cfg, h, pos2, None, cache=(ckv, pc))
            return h, nc
        x, (CKV, P) = jax.lax.scan(body, x, (params["layers"],
                                             cache["ckv"], cache["pos"]))
        new_cache = {"ckv": CKV, "pos": P}
    else:
        window = cfg.sliding_window

        def body(h, xs):
            lp, kc, vc, pc = xs
            h, kv, _ = _decoder_layer_fwd(lp, cfg, h, pos2, window,
                                          cache=(kc, vc, pc))
            return h, kv
        x, (K, V, P) = jax.lax.scan(body, x, (params["layers"],
                                              cache["k"], cache["v"], cache["pos"]))
        new_cache = {"k": K, "v": V, "pos": P}

    if not last:
        return x, new_cache
    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head.astype(x.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits, new_cache


def reset_slots(cfg: ModelConfig, cache: Params, reset: jax.Array) -> Params:
    """Clear the cache of batch slots flagged in ``reset`` (B,) bool: position
    buffers back to -1 (empty), everything else — KV, SSM/conv recurrent
    state — to zero.  Attention caches are already protected from stale
    occupants by kpos masking, but recurrent SSM state is continued
    unconditionally, so a reused slot MUST be wiped before prefill."""
    def one(kp, leaf):
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in kp]
        nstack = 2 if ("groups" in names and names[-1] in ("conv", "ssm")) else 1
        m = reset.reshape([1] * nstack + [-1] + [1] * (leaf.ndim - nstack - 1))
        init = jnp.asarray(-1 if names[-1].endswith("pos") else 0, leaf.dtype)
        return jnp.where(m, init, leaf)

    return jax.tree_util.tree_map_with_path(one, cache)


def mask_cache_update(cfg: ModelConfig, old_cache: Params, new_cache: Params,
                      active: jax.Array) -> Params:
    """Keep updates only for active batch slots (continuous batching: inactive
    slots' spurious decode writes — positional KV or recurrent SSM state —
    are rolled back).  ``active``: (B,) bool."""
    def one(kp, old, new):
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in kp]
        nstack = 2 if ("groups" in names and names[-1] in ("conv", "ssm")) else 1
        m = active.reshape([1] * nstack + [-1] + [1] * (old.ndim - nstack - 1))
        return jnp.where(m, new, old)

    return jax.tree_util.tree_map_with_path(one, old_cache, new_cache)


# --------------------------------------------------------------------------- #
# paged KV cache (block-paged pool shared across slots, prefix reuse)
# --------------------------------------------------------------------------- #
def pageable(cfg: ModelConfig) -> bool:
    """Families whose cache is pure positional KV: dense/moe (incl. pure
    SWA), MLA, vlm.  Recurrent state (ssm/hybrid), encoder-decoder xattn and
    gemma-style local/global pairs stay on the contiguous path."""
    return (cfg.family not in ("ssm", "hybrid")
            and not cfg.is_encoder_decoder
            and cfg.local_global_every == 0)


def paged_window(cfg: ModelConfig) -> Optional[int]:
    """Sliding window for the paged mask.  A paged SWA cache stores every
    position and masks by window instead of ring-rotating, so logical block
    index == absolute position and shared prefix pages stay RoPE-exact."""
    if cfg.sliding_window is not None and cfg.local_global_every == 0:
        return cfg.sliding_window
    return None


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=jnp.bfloat16) -> Params:
    """Zero-filled paged pool pytree.  Physical page 0 is the trash page
    (inactive-lane writes, unmapped page-table entries).  K/V pools are
    ``(L, P, Hkv, page, D)``: one page of one KV head is a contiguous
    ``(page, D)`` tile, the block the fused decode kernel streams."""
    if not pageable(cfg):
        raise ValueError(f"family {cfg.family!r} is not pageable")
    if cfg.mla is not None:
        m = cfg.mla
        return {"ckvp": jnp.zeros(
            (cfg.n_layers, n_pages, page_size,
             m.kv_lora_rank + m.qk_rope_head_dim), dtype)}
    return {"kp": jnp.zeros((cfg.n_layers, n_pages, cfg.n_kv_heads,
                             page_size, cfg.d_head), dtype),
            "vp": jnp.zeros((cfg.n_layers, n_pages, cfg.n_kv_heads,
                             page_size, cfg.d_head), dtype)}


def _paged_decoder_layer_fwd(p: Params, cfg: ModelConfig, x: jax.Array,
                             pos2: jax.Array, window: Optional[int], pool,
                             ptab: jax.Array, lens: jax.Array,
                             widx: jax.Array, use_kernel: bool,
                             interpret: Optional[bool]):
    """Pre-norm decoder layer against the paged pool. Returns (x, new_pool)."""
    h = rmsnorm(x, p["ln1"]["scale"], cfg.norm_eps)
    if cfg.mla is not None:
        attn_out, ckvp = paged_mla_fwd(p["attn"], cfg, h, pos2, pool[0],
                                       ptab, lens, widx)
        new_pool = (ckvp,)
    else:
        attn_out, new_pool = paged_attention_fwd(
            p["attn"], cfg, h, pos2, window, pool[0], pool[1], ptab, lens,
            widx, use_kernel=use_kernel, interpret=interpret)
    x = x + attn_out
    h = rmsnorm(x, p["ln2"]["scale"], cfg.norm_eps)
    x = x + _ffn_fwd(p["ffn"], cfg, h)
    return shard_hidden(x), new_pool


def paged_step(params: Params, cfg: ModelConfig, cache: Params,
               tokens: jax.Array, pos2: jax.Array, ptab: jax.Array,
               active: jax.Array, *, page_size: int, use_kernel: bool = False,
               interpret: Optional[bool] = None) -> Tuple[jax.Array, Params]:
    """Cache-backed forward over a token chunk, paged pool edition.

    tokens/pos2: (B, C) int32; ptab: (B, n_ptab) int32 logical-block →
    physical-page (0 = unmapped/trash); active: (B,) bool.  The write index
    is computed once here and shared by every layer: active lanes scatter
    into their mapped page at ``pos % page_size``, inactive lanes into the
    trash page — no ``reset_slots``/``mask_cache_update`` round-trips, the
    page table itself is the isolation boundary.  Valid kv length per lane
    is derived as ``pos2[:, -1] + 1`` (0 when inactive), i.e. the length
    *after* this chunk lands.
    """
    B, C = tokens.shape
    dtype = compute_dtype(cfg)
    x = params["embed"][tokens].astype(dtype)
    if cfg.local_global_every:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)

    active = active.astype(bool)
    lens = jnp.where(active, pos2[:, -1] + 1, 0).astype(jnp.int32)
    phys = jnp.take_along_axis(ptab.astype(jnp.int32), pos2 // page_size,
                               axis=1)                     # (B, C)
    widx = phys * page_size + pos2 % page_size
    widx = jnp.where(active[:, None], widx,
                     jnp.arange(C, dtype=jnp.int32)[None, :] % page_size)
    window = paged_window(cfg)

    if cfg.mla is not None:
        def body(h, xs):
            lp, ckvp = xs
            h, (c2,) = _paged_decoder_layer_fwd(
                lp, cfg, h, pos2, None, (ckvp,), ptab, lens, widx,
                use_kernel=False, interpret=interpret)
            return h, c2
        x, CKVP = jax.lax.scan(body, x, (params["layers"], cache["ckvp"]))
        new_cache = {"ckvp": CKVP}
    else:
        def body(h, xs):
            lp, kp, vp = xs
            h, kv = _paged_decoder_layer_fwd(
                lp, cfg, h, pos2, window, (kp, vp), ptab, lens, widx,
                use_kernel=use_kernel, interpret=interpret)
            return h, kv
        x, (KP, VP) = jax.lax.scan(body, x, (params["layers"],
                                             cache["kp"], cache["vp"]))
        new_cache = {"kp": KP, "vp": VP}

    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head.astype(x.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits, new_cache


def paged_stage_step(params: Params, cfg: ModelConfig, cache: Params,
                     x: jax.Array, pos2: jax.Array, ptab: jax.Array,
                     active: jax.Array, *, page_size: int, first: bool,
                     last: bool, use_kernel: bool = False,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jax.Array, Params]:
    """Paged forward over ONE pipeline stage's layer slice.

    ``x`` is int32 tokens (B, C) on the first stage and the previous stage's
    hidden state (B, C, D) otherwise; ``cache`` holds the stage's layer
    slice of the paged pool (leading layer axis, pages shared engine-wide
    through the lockstep per-stage pools).  The write-index prelude is
    recomputed per stage from the same (pos2, ptab, active) scalars — it is
    stage-invariant, so every stage scatters into the same page rows of its
    own layer slice.  Composing the stages in order reproduces
    :func:`paged_step` exactly — same scans, same reduction order.
    """
    B, C = pos2.shape
    if first:
        dtype = compute_dtype(cfg)
        x = params["embed"][x].astype(dtype)
        if cfg.local_global_every:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)

    active = active.astype(bool)
    lens = jnp.where(active, pos2[:, -1] + 1, 0).astype(jnp.int32)
    phys = jnp.take_along_axis(ptab.astype(jnp.int32), pos2 // page_size,
                               axis=1)
    widx = phys * page_size + pos2 % page_size
    widx = jnp.where(active[:, None], widx,
                     jnp.arange(C, dtype=jnp.int32)[None, :] % page_size)
    window = paged_window(cfg)

    if cfg.mla is not None:
        def body(h, xs):
            lp, ckvp = xs
            h, (c2,) = _paged_decoder_layer_fwd(
                lp, cfg, h, pos2, None, (ckvp,), ptab, lens, widx,
                use_kernel=False, interpret=interpret)
            return h, c2
        x, CKVP = jax.lax.scan(body, x, (params["layers"], cache["ckvp"]))
        new_cache = {"ckvp": CKVP}
    else:
        def body(h, xs):
            lp, kp, vp = xs
            h, kv = _paged_decoder_layer_fwd(
                lp, cfg, h, pos2, window, (kp, vp), ptab, lens, widx,
                use_kernel=use_kernel, interpret=interpret)
            return h, kv
        x, (KP, VP) = jax.lax.scan(body, x, (params["layers"],
                                             cache["kp"], cache["vp"]))
        new_cache = {"kp": KP, "vp": VP}

    if not last:
        return x, new_cache
    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head.astype(x.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits, new_cache


def extract_paged_slot(cfg: ModelConfig, cache: Params, pages, position: int,
                       page_size: int) -> Params:
    """Gather one request's pages into the *contiguous* extract format
    (:func:`extract_slot`'s layout), so a paged export installs into either
    a contiguous target (``install_slot``) or a paged one
    (``install_paged_slot``) — page-granular migration without a special
    wire format."""
    pages = np.asarray(list(pages), np.int32)
    S_src = int(len(pages)) * page_size
    pos_row = np.where(np.arange(S_src) < position,
                       np.arange(S_src), -1).astype(np.int32)
    if cfg.mla is not None:
        ckv = np.asarray(jax.device_get(cache["ckvp"][:, pages]))
        L = ckv.shape[0]
        return {"ckv": ckv.reshape(L, S_src, -1),
                "pos": np.broadcast_to(pos_row, (L, S_src)).copy()}
    # (L, n, Hkv, page, D) → (L, n·page, Hkv, D)
    k = np.asarray(jax.device_get(cache["kp"][:, pages])).swapaxes(2, 3)
    v = np.asarray(jax.device_get(cache["vp"][:, pages])).swapaxes(2, 3)
    L = k.shape[0]
    return {"k": k.reshape(L, S_src, *k.shape[3:]),
            "v": v.reshape(L, S_src, *v.shape[3:]),
            "pos": np.broadcast_to(pos_row, (L, S_src)).copy()}


def install_paged_slot(cfg: ModelConfig, cache: Params, pages, state: Params,
                       position: int, page_size: int) -> Params:
    """Scatter a contiguous-format slot state into freshly-owned pages.

    ``pages[j]`` is the physical page for logical block j (0 = trash for SWA
    blocks wholly outside the window — their positions are never attended
    again).  Positions must be layer-uniform (true for every pageable
    family); raises :class:`SlotMigrationError` when positions the request
    still attends to are missing from the state or fall in a trash block —
    the caller then falls back to recompute-from-continuation.
    """
    try:
        if cfg.mla is not None:
            dst_leaves, src_leaves = [cache["ckvp"]], [state["ckv"]]
            keys = ["ckvp"]
        else:
            dst_leaves, src_leaves = ([cache["kp"], cache["vp"]],
                                      [state["k"], state["v"]])
            keys = ["kp", "vp"]
        src_pos = np.asarray(state["pos"])
        L, S_src = src_pos.shape
        _require(int(dst_leaves[0].shape[0]) == L,
                 f"layer-stack mismatch: {dst_leaves[0].shape[0]} != {L}")
        _require(bool((src_pos == src_pos[0]).all()),
                 "paged install requires layer-uniform cache positions")
        sp = src_pos[0]
        pages = list(pages)
        n_blocks = len(pages)
        S_buf = n_blocks * page_size
        _require(S_buf >= position,
                 f"{n_blocks} pages cannot hold {position} positions")
        window = paged_window(cfg)
        lo_req = 0 if window is None else max(position - window + 1, 0)
        keep = (sp >= 0) & (sp < position)
        have = np.zeros(S_buf, bool)
        have[sp[keep]] = True
        req = np.zeros(S_buf, bool)
        req[lo_req:position] = True
        for j, pid in enumerate(pages):
            if pid == 0:
                req_blk = req[j * page_size:(j + 1) * page_size]
                _require(not req_blk.any(),
                         "still-visible positions mapped to the trash page")
        _require(not (req & ~have).any(),
                 "state lacks positions the request still attends to")
        jsel = [j for j, pid in enumerate(pages) if pid != 0]
        pidx = np.asarray([pages[j] for j in jsel], np.int32)
        new_cache = dict(cache)
        head_major = cfg.mla is None     # K/V pools hold (Hkv, page, D) pages
        for key, dst, src in zip(keys, dst_leaves, src_leaves):
            row = ((dst.shape[2],) + tuple(dst.shape[4:]) if head_major
                   else tuple(dst.shape[3:]))
            _require(src.shape[0] == L and src.shape[1] == S_src
                     and tuple(src.shape[2:]) == row,
                     f"state shape {tuple(src.shape)} incompatible with "
                     f"pool {tuple(dst.shape)}")
            buf = np.zeros((L, S_buf) + tuple(src.shape[2:]), dtype=dst.dtype)
            buf[:, sp[keep]] = src[:, keep]
            blocks = buf.reshape(L, n_blocks, page_size, *buf.shape[2:])
            if head_major:
                blocks = blocks.swapaxes(2, 3)
            new_cache[key] = dst.at[:, pidx].set(
                jnp.asarray(blocks[:, jsel], dst.dtype))
        return new_cache
    except SlotMigrationError:
        raise
    except (KeyError, IndexError, TypeError, ValueError) as e:
        raise SlotMigrationError(
            f"slot state incompatible with paged pool: {e}") from e


# --------------------------------------------------------------------------- #
# per-slot cache migration (live KV/SSM state transfer across engines)
# --------------------------------------------------------------------------- #
class SlotMigrationError(ValueError):
    """A slot state cannot be installed into the target cache — shape/config
    mismatch, or the target buffers cannot hold the positions the request
    still attends to."""


def _stack_depth(key_path) -> int:
    """Leading layer-stack dims before the batch axis (2 for hybrid group
    SSM leaves, 1 everywhere else) — same rule as reset_slots."""
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in key_path]
    return 2 if ("groups" in names and names[-1] in ("conv", "ssm")) else 1


def extract_slot(cfg: ModelConfig, cache: Params, slot: int) -> Params:
    """Slice one batch slot's KV/SSM state out of ``cache`` as a host copy.

    The result mirrors the cache pytree with the batch axis removed.  Position
    buffers keep their *absolute* positions, which lets :func:`install_slot`
    re-derive physical buffer indices on a target whose buffer length differs
    (rolling SWA rings are rotated by position, not copied by index).
    """
    def one(kp, leaf):
        idx = (slice(None),) * _stack_depth(kp) + (slot,)
        return np.asarray(jax.device_get(leaf[idx]))

    return jax.tree_util.tree_map_with_path(one, cache)


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SlotMigrationError(msg)


def _install_copy(dst: jax.Array, src: np.ndarray, slot: int,
                  nstack: int = 1) -> jax.Array:
    """Position-independent state (SSM/conv recurrent state, xattn KV)."""
    want = dst.shape[:nstack] + dst.shape[nstack + 1:]
    _require(tuple(src.shape) == tuple(want),
             f"state shape {tuple(src.shape)} != cache slot shape {tuple(want)}")
    idx = (slice(None),) * nstack + (slot,)
    return dst.at[idx].set(jnp.asarray(src, dst.dtype))


def _install_attn(dst_leaves, src_leaves, dst_pos: jax.Array,
                  src_pos: np.ndarray, slot: int,
                  window: Optional[int], position: int):
    """Scatter one slot's attention entries into the target buffers by
    absolute position.

    dst leaves: (N, B, S_dst, ...) device arrays sharing ``dst_pos``
    (N, B, S_dst); src leaves: (N, S_src, ...) host arrays sharing
    ``src_pos`` (N, S_src).  Non-rolling buffers index by position directly;
    rolling (``window`` given) buffers index by ``position % S_dst`` — the
    rotation that makes a ring portable across buffer lengths.  Entries the
    target ring cannot hold are dropped only when the request can no longer
    attend to them; otherwise the install is refused.
    """
    N, S_src = src_pos.shape
    _require(dst_pos.shape[0] == N,
             f"layer-stack mismatch: {dst_pos.shape[0]} != {N}")
    S_dst = int(dst_pos.shape[2])
    valid = src_pos >= 0
    if window is None:
        _require(position < S_dst,
                 f"next decode position {position} outside target buffer "
                 f"of length {S_dst}")
        _require(not valid.any() or int(src_pos.max()) < S_dst,
                 f"cached position {int(src_pos.max())} outside target "
                 f"buffer of length {S_dst}")
        keep = valid
        dest = np.where(valid, src_pos, 0)
    else:
        keep = valid & (src_pos >= position - S_dst)
        needed = valid & (src_pos > position - window)
        _require(not (needed & ~keep).any(),
                 f"target ring of length {S_dst} cannot hold the positions "
                 f"still visible inside window {window}")
        dest = np.where(keep, src_pos, 0) % S_dst
    n_idx, s_idx = np.nonzero(keep)
    d_idx = dest[n_idx, s_idx]

    out = []
    for dst, src in zip(dst_leaves, src_leaves):
        _require(tuple(src.shape[2:]) == tuple(dst.shape[3:])
                 and src.shape[0] == N and src.shape[1] == S_src,
                 f"attention state shape {tuple(src.shape)} incompatible "
                 f"with cache {tuple(dst.shape)}")
        buf = np.zeros((N, S_dst) + tuple(dst.shape[3:]), dtype=dst.dtype)
        buf[n_idx, d_idx] = src[n_idx, s_idx]
        out.append(dst.at[:, slot].set(jnp.asarray(buf)))
    posbuf = np.full((N, S_dst), -1, np.int32)
    posbuf[n_idx, d_idx] = src_pos[n_idx, s_idx]
    out.append(dst_pos.at[:, slot].set(jnp.asarray(posbuf)))
    return out


def install_slot(cfg: ModelConfig, cache: Params, slot: int, state: Params,
                 position: int) -> Params:
    """Install an :func:`extract_slot` state into batch slot ``slot``.

    ``position`` is the request's next decode position (its cache holds
    positions < ``position``).  The whole slot is overwritten — including
    entries the state does not cover — so a previous occupant can never
    leak through.  Raises :class:`SlotMigrationError` when the state cannot
    be represented in the target cache (different architecture shapes, or a
    buffer too short for the still-visible positions); the caller then falls
    back to recompute-from-continuation.
    """
    try:
        if cfg.family == "ssm":
            return {"conv": _install_copy(cache["conv"], state["conv"], slot),
                    "ssm": _install_copy(cache["ssm"], state["ssm"], slot)}
        if cfg.family == "hybrid":
            new = {"groups": {
                "conv": _install_copy(cache["groups"]["conv"],
                                      state["groups"]["conv"], slot, nstack=2),
                "ssm": _install_copy(cache["groups"]["ssm"],
                                     state["groups"]["ssm"], slot, nstack=2)}}
            k, v, pos = _install_attn(
                [cache["attn_k"], cache["attn_v"]],
                [state["attn_k"], state["attn_v"]],
                cache["attn_pos"], state["attn_pos"], slot, None, position)
            new.update(attn_k=k, attn_v=v, attn_pos=pos)
            if "tail" in cache:
                _require("tail" in state, "state lacks the mamba tail stack")
                new["tail"] = {
                    "conv": _install_copy(cache["tail"]["conv"],
                                          state["tail"]["conv"], slot),
                    "ssm": _install_copy(cache["tail"]["ssm"],
                                         state["tail"]["ssm"], slot)}
            return new
        if cfg.mla is not None:
            ckv, pos = _install_attn([cache["ckv"]], [state["ckv"]],
                                     cache["pos"], state["pos"], slot,
                                     None, position)
            return {"ckv": ckv, "pos": pos}
        if cfg.local_global_every == 2:
            lk, lv, lpos = _install_attn(
                [cache["loc_k"], cache["loc_v"]],
                [state["loc_k"], state["loc_v"]],
                cache["loc_pos"], state["loc_pos"], slot,
                cfg.sliding_window, position)
            gk, gv, gpos = _install_attn(
                [cache["glob_k"], cache["glob_v"]],
                [state["glob_k"], state["glob_v"]],
                cache["glob_pos"], state["glob_pos"], slot, None, position)
            return {"loc_k": lk, "loc_v": lv, "loc_pos": lpos,
                    "glob_k": gk, "glob_v": gv, "glob_pos": gpos}
        if cfg.is_encoder_decoder:
            k, v, pos = _install_attn([cache["k"], cache["v"]],
                                      [state["k"], state["v"]],
                                      cache["pos"], state["pos"], slot,
                                      None, position)
            return {"k": k, "v": v, "pos": pos,
                    "xk": _install_copy(cache["xk"], state["xk"], slot),
                    "xv": _install_copy(cache["xv"], state["xv"], slot)}
        # dense / moe: a pure-SWA arch rolls its single KV buffer
        window = (cfg.sliding_window
                  if cfg.sliding_window is not None
                  and cfg.local_global_every == 0 else None)
        k, v, pos = _install_attn([cache["k"], cache["v"]],
                                  [state["k"], state["v"]],
                                  cache["pos"], state["pos"], slot,
                                  window, position)
        return {"k": k, "v": v, "pos": pos}
    except SlotMigrationError:
        raise
    except (KeyError, IndexError, TypeError, ValueError) as e:
        raise SlotMigrationError(
            f"slot state incompatible with target cache: {e}") from e
