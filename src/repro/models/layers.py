"""Core transformer layers — pure-functional JAX, params as nested dicts.

All functions take explicit params and are shape-polymorphic over batch/seq.
Attention supports GQA, sliding windows, logit softcaps, MLA, KV caches and
query-chunking (keeps the S×S score tensor bounded for 32k prefill lowering).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig

from repro.models import flags

Params = Dict[str, Any]

NEG_INF = -2.0 ** 30  # large-negative that survives bf16


def shard_hidden(x: jax.Array) -> jax.Array:
    """Apply the per-cell activation sharding constraint (B, S, ...) if set."""
    spec = flags.get_flag("act_shard")
    if spec is None:
        return x
    from jax.sharding import PartitionSpec as P
    ent = []
    b = spec["batch"]
    ent.append(b if (b is not None and x.shape[0] % spec["batch_size"] == 0) else None)
    if x.ndim >= 3:
        s = spec["seq"]
        ent.append(s if (s is not None and x.shape[1] % spec["seq_size"] == 0) else None)
        ent.extend([None] * (x.ndim - 2))
    else:
        ent.extend([None] * (x.ndim - 1))
    return jax.lax.with_sharding_constraint(x, P(*ent))


# --------------------------------------------------------------------------- #
# norms / embeddings / positional
# --------------------------------------------------------------------------- #
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def init_rmsnorm(d: int) -> Params:
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                                   # (D/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv         # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# --------------------------------------------------------------------------- #
# dense projections
# --------------------------------------------------------------------------- #
def _uniform(key, shape, scale, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, -scale, scale)


def init_linear(key, d_in: int, d_out: int, bias: bool = False) -> Params:
    s = 1.0 / math.sqrt(d_in)
    p = {"w": _uniform(key, (d_in, d_out), s)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def linear(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# --------------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------------- #
def init_attention(key, cfg: ModelConfig) -> Params:
    d, h, hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": _uniform(ks[0], (d, h * dh), s),
        "wk": _uniform(ks[1], (d, hk * dh), s),
        "wv": _uniform(ks[2], (d, hk * dh), s),
        "wo": _uniform(ks[3], (h * dh, d), 1.0 / math.sqrt(h * dh)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), jnp.float32)
        p["bk"] = jnp.zeros((hk * dh,), jnp.float32)
        p["bv"] = jnp.zeros((hk * dh,), jnp.float32)
    return p


def _attn_mask(q_pos: jax.Array, k_pos: jax.Array, window: Optional[int],
               causal: bool = True) -> jax.Array:
    """(..., Sq, Sk) boolean mask. q_pos: (B,Sq), k_pos: (B,Sk)."""
    diff = q_pos[:, :, None] - k_pos[:, None, :]       # (B, Sq, Sk)
    mask = (diff >= 0) if causal else jnp.ones_like(diff, dtype=bool)
    if window is not None:
        mask = mask & (diff < window)
    return mask[:, None, :, :]                          # (B, 1, Sq, Sk)


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
         logit_cap: Optional[float] = None, scale: Optional[float] = None,
         q_chunk: int = 0) -> jax.Array:
    """Grouped-query scaled dot-product attention.

    q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D); mask: (B, 1, Sq, Sk) bool.
    Chunked over queries when q_chunk > 0 and Sq > q_chunk to bound the score
    tensor at (q_chunk, Sk) — required for 32k×32k prefill lowering.
    """
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if rep > 1:
        # explicit KV repeat → every einsum below is cleanly head-shardable
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    score_dt = (jnp.bfloat16 if flags.get_flag("attn_scores") == "bf16"
                else jnp.float32)

    def block(qb, mb):
        # qb: (B, sq, H, D), mb: (B, 1, sq, Sk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                       preferred_element_type=score_dt) * jnp.asarray(
                           scale, score_dt)
        s = softcap(s, logit_cap)
        s = jnp.where(mb, s, jnp.asarray(NEG_INF, score_dt))
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
        return o

    if q_chunk and Sq > q_chunk and Sq % q_chunk == 0:
        n = Sq // q_chunk
        qc = q.reshape(B, n, q_chunk, H, D).transpose(1, 0, 2, 3, 4)
        mc = mask.reshape(B, 1, n, q_chunk, -1).transpose(2, 0, 1, 3, 4)
        oc = jax.lax.map(lambda args: block(*args), (qc, mc))
        return oc.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, D)
    return block(q, mask)


def attention_fwd(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                  window: Optional[int], kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
                  cache_positions: Optional[jax.Array] = None,
                  xattn_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                  causal: bool = True,
                  q_chunk: int = 2048) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Standard GQA attention. Returns (out, (k, v) new cache entries).

    * full-sequence mode: kv_cache is None → self-attention over x.
    * decode mode: kv_cache = (K, V) buffers (B, S_max, Hkv, D); x is (B, 1, d);
      new K/V written at ``positions`` and attention runs over the buffer.
    * cross-attention mode: xattn_kv provides fixed (K, V) (whisper decoder).
    """
    B, S, _ = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head

    q = x @ p["wq"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(B, S, H, D)

    if xattn_kv is not None:
        k, v = xattn_kv
        kpos = jnp.broadcast_to(jnp.arange(k.shape[1], dtype=jnp.int32)[None], (B, k.shape[1]))
        mask = _attn_mask(positions, kpos, None, causal=False)
        out = sdpa(q, k, v, mask, cfg.attn_logit_softcap, q_chunk=q_chunk)
        return out.reshape(B, S, H * D) @ p["wo"].astype(x.dtype), (k, v)

    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bk" in p:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    k = k.reshape(B, S, Hkv, D)
    v = v.reshape(B, S, Hkv, D)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if kv_cache is not None:
        K, V = kv_cache
        S_max = K.shape[1]
        # rolling buffer for sliding-window archs
        slot = positions % S_max if window is not None else positions
        K = jax.vmap(lambda buf, kk, i: jax.lax.dynamic_update_slice(buf, kk, (i, 0, 0)))(
            K, k, slot[:, 0])
        V = jax.vmap(lambda buf, vv, i: jax.lax.dynamic_update_slice(buf, vv, (i, 0, 0)))(
            V, v, slot[:, 0])
        kpos = cache_positions  # (B, S_max) absolute positions of buffer slots
        kpos = jax.vmap(lambda cp, pp, i: jax.lax.dynamic_update_slice(cp, pp, (i,)))(
            kpos, positions, slot[:, 0])
        mask = _attn_mask(positions, kpos, window) & (kpos >= 0)[:, None, None, :]
        out = sdpa(q, K, V, mask, cfg.attn_logit_softcap)
        new_cache = (K, V, kpos)
    else:
        mask = _attn_mask(positions, positions, window)
        out = sdpa(q, k, v, mask, cfg.attn_logit_softcap, q_chunk=q_chunk)
        new_cache = (k, v)

    return out.reshape(B, S, H * D) @ p["wo"].astype(x.dtype), new_cache


# --------------------------------------------------------------------------- #
# paged attention (block-paged KV pool shared across batch slots)
# --------------------------------------------------------------------------- #
def paged_attention_fwd(p: Params, cfg: ModelConfig, x: jax.Array,
                        pos2: jax.Array, window: Optional[int],
                        kp: jax.Array, vp: jax.Array, ptab: jax.Array,
                        lens: jax.Array, widx: jax.Array,
                        use_kernel: bool = False,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """GQA attention against a shared paged KV pool.

    x: (B, C, d) token chunk at absolute positions ``pos2`` (B, C);
    kp/vp: (P, Hkv, page, D) physical page pools — head-major inside a
    page, so the decode kernel's block per (page, KV head) is one aligned
    ``(page, D)`` tile; ptab: (B, n_ptab) int32
    logical-block → physical-page map; lens: (B,) valid kv length *after*
    this chunk's writes; widx: (B, C) int32 flat pool row (page·page_size +
    offset) each token writes to — precomputed by the caller, with inactive
    batch lanes diverted into the trash page, which replaces the contiguous
    path's ``mask_cache_update`` rollback.

    Unlike the rolling contiguous SWA cache, a paged sliding-window cache
    stores *every* position and masks by window — logical index == absolute
    position, so shared prefix pages are position-exact under RoPE.
    """
    B, C, _ = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    page = kp.shape[2]

    q = x @ p["wq"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(B, C, H, D)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bk" in p:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    k = k.reshape(B, C, Hkv, D)
    v = v.reshape(B, C, Hkv, D)
    q = apply_rope(q, pos2, cfg.rope_theta)
    k = apply_rope(k, pos2, cfg.rope_theta)

    flat = widx.reshape(-1)
    wpage, woff = flat // page, flat % page
    new_kp = kp.at[wpage, :, woff].set(k.reshape(B * C, Hkv, D))
    new_vp = vp.at[wpage, :, woff].set(v.reshape(B * C, Hkv, D))

    if use_kernel and C == 1 and cfg.attn_logit_softcap is None:
        from repro.kernels.flash_decode import ops as fd_ops
        shard = flags.get_flag("paged_shard")
        if shard is not None:
            # head-sharded pool: explicit shard_map (pallas_call has no
            # GSPMD rule); each shard decodes its own KV-head slice
            out = fd_ops.sharded_paged_flash_decode(
                q[:, 0], new_kp, new_vp, ptab, lens, shard["mesh"],
                axis=shard.get("axis", "model"), window=window,
                interpret=interpret)[:, None]
        else:
            out = fd_ops.paged_flash_decode_head_slice(
                q[:, 0], new_kp, new_vp, ptab, lens, 0, Hkv, window=window,
                interpret=interpret)[:, None]
    else:
        S = ptab.shape[1] * page
        # gather mapped pages: (B, n_ptab, Hkv, page, D) → (B, S, Hkv, D)
        K = new_kp[ptab].swapaxes(2, 3).reshape(B, S, Hkv, D)
        V = new_vp[ptab].swapaxes(2, 3).reshape(B, S, Hkv, D)
        kpos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        mask = (_attn_mask(pos2, kpos, window)
                & (kpos < lens[:, None])[:, None, None, :])
        out = sdpa(q, K, V, mask, cfg.attn_logit_softcap)

    return out.reshape(B, C, H * D) @ p["wo"].astype(x.dtype), (new_kp, new_vp)


def paged_mla_fwd(p: Params, cfg: ModelConfig, x: jax.Array, pos2: jax.Array,
                  ckvp: jax.Array, ptab: jax.Array, lens: jax.Array,
                  widx: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """MLA attention against a paged latent pool ckvp (P, page, r + d_rope).

    Same page-table/trash-write contract as :func:`paged_attention_fwd`; the
    absorbed-matrix decode trick is unchanged — only the latent cache moves
    from a per-slot buffer into shared pages.
    """
    m: MLAConfig = cfg.mla
    B, C, _ = x.shape
    H = cfg.n_heads
    r, dr, dn, dv = m.kv_lora_rank, m.qk_rope_head_dim, m.qk_nope_head_dim, m.v_head_dim
    P, page = ckvp.shape[0], ckvp.shape[1]
    scale = 1.0 / math.sqrt(dn + dr)

    q = (x @ p["wq_a"].astype(x.dtype)) @ p["wq_b"].astype(x.dtype)
    q = q.reshape(B, C, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, pos2, cfg.rope_theta)

    ckv = x @ p["wkv_a"].astype(x.dtype)                  # (B, C, r + dr)
    c_lat, k_rope = ckv[..., :r], ckv[..., r:]
    k_rope = apply_rope(k_rope[:, :, None, :], pos2, cfg.rope_theta)[:, :, 0]
    ckv = jnp.concatenate([c_lat, k_rope], axis=-1)

    new_ckvp = ckvp.reshape(P * page, r + dr).at[widx.reshape(-1)].set(
        ckv.reshape(B * C, r + dr)).reshape(P, page, r + dr)

    wk_b = p["wk_b"].astype(x.dtype).reshape(r, H, dn)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wk_b)

    S = ptab.shape[1] * page
    Ckv = new_ckvp[ptab].reshape(B, S, r + dr)
    c_k, kr = Ckv[..., :r], Ckv[..., r:]
    kpos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    mask = (_attn_mask(pos2, kpos, None)
            & (kpos < lens[:, None])[:, None, None, :])

    s = (jnp.einsum("bshr,bkr->bhsk", q_lat, c_k,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bshd,bkd->bhsk", q_rope, kr,
                      preferred_element_type=jnp.float32))
    s = jnp.where(mask, s * scale, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhsk,bkr->bshr", pr, c_k)

    wv_b = p["wv_b"].astype(x.dtype).reshape(r, H, dv)
    o = jnp.einsum("bshr,rhd->bshd", o_lat, wv_b).reshape(B, C, H * dv)
    return o @ p["wo"].astype(x.dtype), new_ckvp


# --------------------------------------------------------------------------- #
# MLA (Multi-head Latent Attention — MiniCPM3 / DeepSeek-V2)
# --------------------------------------------------------------------------- #
def init_mla(key, cfg: ModelConfig) -> Params:
    m: MLAConfig = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    s = 1.0 / math.sqrt(d)
    return {
        "wq_a": _uniform(ks[0], (d, m.q_lora_rank), s),
        "wq_b": _uniform(ks[1], (m.q_lora_rank, H * qd), 1.0 / math.sqrt(m.q_lora_rank)),
        "wkv_a": _uniform(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), s),
        "wk_b": _uniform(ks[3], (m.kv_lora_rank, H * m.qk_nope_head_dim),
                         1.0 / math.sqrt(m.kv_lora_rank)),
        "wv_b": _uniform(ks[4], (m.kv_lora_rank, H * m.v_head_dim),
                         1.0 / math.sqrt(m.kv_lora_rank)),
        "wo": _uniform(ks[5], (H * m.v_head_dim, d), 1.0 / math.sqrt(H * m.v_head_dim)),
    }


def mla_fwd(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
            kv_cache: Optional[jax.Array] = None,
            cache_positions: Optional[jax.Array] = None,
            q_chunk: int = 2048) -> Tuple[jax.Array, jax.Array]:
    """MLA attention. Cache stores the COMPRESSED latent (B, S, r + d_rope).

    Decode uses the absorbed-matrix trick: scores are computed in latent space
    (q_nope @ Wk_b folds into q), so per-token KV bytes = r + d_rope only.
    """
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    r, dr, dn, dv = m.kv_lora_rank, m.qk_rope_head_dim, m.qk_nope_head_dim, m.v_head_dim
    scale = 1.0 / math.sqrt(dn + dr)

    q = (x @ p["wq_a"].astype(x.dtype)) @ p["wq_b"].astype(x.dtype)
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = x @ p["wkv_a"].astype(x.dtype)                 # (B, S, r + dr)
    c_lat, k_rope = ckv[..., :r], ckv[..., r:]
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    ckv = jnp.concatenate([c_lat, k_rope], axis=-1)

    wk_b = p["wk_b"].astype(x.dtype).reshape(r, H, dn)
    # absorbed query: (B,S,H,r)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wk_b)

    if kv_cache is not None:
        Ckv = kv_cache                                   # (B, S_max, r + dr)
        Ckv = jax.vmap(lambda buf, cc, i: jax.lax.dynamic_update_slice(buf, cc, (i, 0)))(
            Ckv, ckv, positions[:, 0])
        kpos = jax.vmap(lambda cp, pp, i: jax.lax.dynamic_update_slice(cp, pp, (i,)))(
            cache_positions, positions, positions[:, 0])
        new_cache = (Ckv, kpos)
        c_k, kr = Ckv[..., :r], Ckv[..., r:]
        valid = (kpos >= 0)
    else:
        c_k, kr = c_lat, k_rope
        kpos = positions
        valid = jnp.ones_like(kpos, dtype=bool)
        new_cache = (ckv, kpos)

    mask = _attn_mask(positions, kpos, None) & valid[:, None, None, :]

    def block(q_lat_b, q_rope_b, mask_b):
        s = (jnp.einsum("bshr,bkr->bhsk", q_lat_b, c_k, preferred_element_type=jnp.float32)
             + jnp.einsum("bshd,bkd->bhsk", q_rope_b, kr, preferred_element_type=jnp.float32))
        s = jnp.where(mask_b, s * scale, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhsk,bkr->bshr", pr, c_k)    # (B,sq,H,r)
        return o_lat

    if q_chunk and S > q_chunk and S % q_chunk == 0:
        n = S // q_chunk
        ql = q_lat.reshape(B, n, q_chunk, H, r).transpose(1, 0, 2, 3, 4)
        qr = q_rope.reshape(B, n, q_chunk, H, dr).transpose(1, 0, 2, 3, 4)
        mc = mask.reshape(B, 1, n, q_chunk, -1).transpose(2, 0, 1, 3, 4)
        o_lat = jax.lax.map(lambda a: block(*a), (ql, qr, mc))
        o_lat = o_lat.transpose(1, 0, 2, 3, 4).reshape(B, S, H, r)
    else:
        o_lat = block(q_lat, q_rope, mask)

    wv_b = p["wv_b"].astype(x.dtype).reshape(r, H, dv)
    o = jnp.einsum("bshr,rhd->bshd", o_lat, wv_b).reshape(B, S, H * dv)
    return o @ p["wo"].astype(x.dtype), new_cache


# --------------------------------------------------------------------------- #
# feed-forward: SwiGLU + MoE
# --------------------------------------------------------------------------- #
def init_swiglu(key, d: int, d_ff: int) -> Params:
    ks = jax.random.split(key, 3)
    s = 1.0 / math.sqrt(d)
    return {
        "w_gate": _uniform(ks[0], (d, d_ff), s),
        "w_up": _uniform(ks[1], (d, d_ff), s),
        "w_down": _uniform(ks[2], (d_ff, d), 1.0 / math.sqrt(d_ff)),
    }


def swiglu(p: Params, x: jax.Array) -> jax.Array:
    g = jax.nn.silu(x @ p["w_gate"].astype(x.dtype))
    u = x @ p["w_up"].astype(x.dtype)
    return (g * u) @ p["w_down"].astype(x.dtype)


def init_moe(key, cfg: ModelConfig) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    return {
        "router": _uniform(ks[0], (d, e), s),
        "w_gate": _uniform(ks[1], (e, d, f), s),
        "w_up": _uniform(ks[2], (e, d, f), s),
        "w_down": _uniform(ks[3], (e, f, d), 1.0 / math.sqrt(f)),
    }


def moe_gates(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Dense router gates (B,S,E): renormalised top-k probs scattered back
    into the full expert axis, zeros elsewhere.  Shared by the dense-mix
    baseline and the expert-parallel shard_map path — gating is computed
    replicated in both, so sharded and unsharded runs see identical gates."""
    B, S, _ = x.shape
    logits = x @ p["router"].astype(x.dtype)                       # (B,S,E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None], top_i].set(top_p)


def moe_dense_mix(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Baseline (paper-faithful naive) MoE: compute ALL experts, weighted-sum.

    Simple/robust under pjit; FLOPs = full-expert (the §Perf hillclimb replaces
    this with capacity-based dispatch, see moe_dispatch below).
    """
    gate_full = moe_gates(p, cfg, x)
    g = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["w_gate"].astype(x.dtype)))
    u = jnp.einsum("bsd,edf->bsef", x, p["w_up"].astype(x.dtype))
    y = jnp.einsum("bsef,efd->bsed", g * u, p["w_down"].astype(x.dtype))
    return jnp.einsum("bsed,bse->bsd", y, gate_full.astype(x.dtype))


def moe_dispatch(p: Params, cfg: ModelConfig, x: jax.Array,
                 capacity_factor: float = 1.25) -> jax.Array:
    """Capacity-based scatter dispatch MoE (the optimized path).

    Tokens are scattered into per-expert buffers of fixed capacity, expert
    FFNs run as grouped batched matmuls, outputs gathered back weighted by
    router probs.  FLOPs ≈ active-expert only (+ capacity slack).

    Dispatch is BATCH-ROW-LOCAL (capacity per sequence): the scatter/gather
    never crosses the batch sharding axis, so under pjit no cross-shard
    collectives are generated by routing — §Perf iteration 2 (the global-
    buffer variant all-reduced multi-TB scatter contributions; refuted).
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    if S == 1 and B > 1:
        # decode: the whole (tiny) batch is one dispatch row — per-expert
        # buffers amortise across tokens, compute ≈ active experts only
        y = moe_dispatch(p, cfg, x.reshape(1, B, d), capacity_factor)
        return y.reshape(B, S, d)
    C = max(int(math.ceil(S * K / E * capacity_factor)), 1)

    # routing cumsum/scatter must not span the seq (model-axis) shards:
    # constrain the dispatch region to batch-only sharding (§Perf iter. 3)
    spec = flags.get_flag("act_shard")
    if spec is not None:
        from jax.sharding import PartitionSpec as P
        b = spec["batch"] if (spec["batch"] is not None
                              and B % spec["batch_size"] == 0) else None
        x = jax.lax.with_sharding_constraint(x, P(b, None, None))

    logits = x @ p["router"].astype(x.dtype)                       # (B,S,E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, K)                         # (B,S,K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    def dispatch_row(xr, er, wr):
        # xr: (S, d); er: (S, K) expert ids; wr: (S, K) probs
        flat_e = er.reshape(-1)                                    # (S·K,)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
        keep = pos < C
        slot = flat_e * C + jnp.where(keep, pos, 0)
        src = jnp.repeat(xr, K, axis=0) * keep[:, None].astype(xr.dtype)
        buf = jnp.zeros((E * C, d), xr.dtype).at[slot].add(src, mode="drop")
        return buf.reshape(E, C, d), slot, (wr.reshape(-1) * keep)

    buf, slot, w = jax.vmap(dispatch_row)(x, top_i, top_p)         # (B,E,C,d)

    g = jax.nn.silu(jnp.einsum("becd,edf->becf", buf,
                               p["w_gate"].astype(x.dtype)))
    u = jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(x.dtype))
    yb = jnp.einsum("becf,efd->becd", g * u, p["w_down"].astype(x.dtype))
    yb = yb.reshape(B, E * C, d)

    y = jnp.take_along_axis(yb, slot[..., None], axis=1)           # (B,S·K,d)
    y = (y * w[..., None].astype(x.dtype)).reshape(B, S, K, d).sum(axis=2)
    return shard_hidden(y)
