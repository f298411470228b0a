"""Plain float32 reference of the served models, written from their
published descriptions and nothing of the program.

It covers a decoder-only transformer with grouped-query attention, optional
QKV bias, rotary position embeddings (rotate-half), RMSNorm, SwiGLU feed-
forward or a top-k-of-E mixture of SwiGLU experts (softmax router, top-k
renormalised), and a tied or separate LM head — Qwen2 and Mixtral.  The
architecture is read from the configuration's Hugging Face keys.

``scores`` runs one sequence, layer by layer, and reduces the logits in
row blocks to what a comparison needs: each position's best logit, the
logit of a given target token, and the argmax; for a mixture of experts
also each position's router margin, the smallest over the layers of the
gap between the k-th and the (k+1)-th largest router logit, which says how
near the choice of experts lies to a tie.  In ``"f32"`` mode every
product is float32 at ``Precision.HIGHEST``.  ``"fp8"`` mode is the
lower-precision control: weights rounded to float8_e4m3fn with one scale per
output channel, activations and the residual stream in bfloat16.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 256
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


@dataclass(frozen=True)
class Arch:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    experts: int              # 0: dense SwiGLU
    top_k: int
    tied: bool
    qkv_bias: bool
    eps: float
    theta: float


def arch(hf: dict) -> Arch:
    """The architecture stated by a configuration file's Hugging Face keys."""
    d, heads = int(hf["hidden_size"]), int(hf["num_attention_heads"])
    return Arch(
        d=d, layers=int(hf["num_hidden_layers"]), heads=heads,
        kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf.get("head_dim") or d // heads),
        ffn=int(hf["intermediate_size"]), vocab=int(hf["vocab_size"]),
        experts=int(hf.get("num_local_experts", 0)),
        top_k=int(hf.get("num_experts_per_tok", 0)),
        tied=bool(hf["tie_word_embeddings"]), qkv_bias=bool(hf["qkv_bias"]),
        eps=float(hf["rms_norm_eps"]), theta=float(hf["rope_theta"]))


# --------------------------------------------------------------------------- #
# precision modes
# --------------------------------------------------------------------------- #
def _act(mode: str):
    return jnp.float32 if mode == "f32" else jnp.bfloat16


def _w(w: jax.Array, mode: str, axis: int = -2) -> jax.Array:
    """A weight as the mode multiplies it: float32, or float8 with one scale
    per output channel (max over the contracted ``axis``), widened back to
    bfloat16 for the product."""
    if mode == "f32":
        return w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return ((w / scale).astype(FP8).astype(jnp.float32) * scale
            ).astype(jnp.bfloat16)


def _mm(x, w, mode):
    return jnp.matmul(x, _w(w, mode), precision=HIGHEST,
                      preferred_element_type=jnp.float32).astype(_act(mode))


def _rms(x, weight, eps, mode):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(_act(mode))


def _rope(x, pos, theta):
    """Rotate-half rotary embedding; x (S, heads, D), pos (S,)."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x32 = x.astype(jnp.float32)
    half = D // 2
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


# --------------------------------------------------------------------------- #
# one layer
# --------------------------------------------------------------------------- #
def _attention(a: Arch, lw: Dict[str, jax.Array], h, pos, mode):
    S = h.shape[0]
    q = _mm(h, lw["wq"], mode)
    k = _mm(h, lw["wk"], mode)
    v = _mm(h, lw["wv"], mode)
    if a.qkv_bias:
        q = q + lw["bq"].astype(q.dtype)
        k = k + lw["bk"].astype(k.dtype)
        v = v + lw["bv"].astype(v.dtype)
    G = a.heads // a.kv_heads
    q = _rope(q.reshape(S, a.heads, a.head_dim), pos, a.theta)
    k = _rope(k.reshape(S, a.kv_heads, a.head_dim), pos, a.theta)
    v = v.reshape(S, a.kv_heads, a.head_dim)
    q = q.reshape(S, a.kv_heads, G, a.head_dim)
    s = jnp.einsum("skgd,tkd->kgst", q, k, precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(a.head_dim)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(_act(mode))
    o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HIGHEST,
                   preferred_element_type=jnp.float32).astype(_act(mode))
    return _mm(o.reshape(S, a.heads * a.head_dim), lw["wo"], mode)


def _swiglu(x, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(x, wg, mode)) * _mm(x, wu, mode), wd, mode)


def _moe(a: Arch, lw, h, mode):
    """Top-k of E experts: softmax over the router's logits, the k largest
    renormalised to sum to one; each expert's SwiGLU output weighted by its
    gate, zero for the experts not chosen.  Returns the output and, at each
    position, the k-th largest router logit less the (k+1)-th (+inf where
    every expert is chosen)."""
    logits = jnp.matmul(h, _w(lw["router"], mode), precision=HIGHEST,
                        preferred_element_type=jnp.float32)
    if a.top_k < a.experts:
        kth = jax.lax.top_k(logits, a.top_k + 1)[0]
        margin = kth[:, -2] - kth[:, -1]
    else:
        margin = jnp.full(logits.shape[:1], jnp.inf, jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, a.top_k)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(top_i, a.experts, dtype=jnp.float32)
                   * top_p[..., None], axis=-2)                  # (S, E)

    def one(acc, e):
        y = _swiglu(h, lw["w_gate"][e], lw["w_up"][e], lw["w_down"][e], mode)
        return acc + gate[:, e, None] * y.astype(jnp.float32), None

    out, _ = jax.lax.scan(one, jnp.zeros(h.shape, jnp.float32),
                          jnp.arange(a.experts))
    return out.astype(_act(mode)), margin


def _layer(a: Arch, mode: str, x, lw, pos):
    """One layer; returns the new residual stream and the layer's router
    margin (None for a dense layer)."""
    h = _rms(x, lw["attn_norm"], a.eps, mode)
    x = x + _attention(a, lw, h, pos, mode)
    h = _rms(x, lw["mlp_norm"], a.eps, mode)
    if a.experts:
        y, margin = _moe(a, lw, h, mode)
        return x + y, margin
    return x + _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], mode), None


# --------------------------------------------------------------------------- #
# a whole sequence
# --------------------------------------------------------------------------- #
def _forward(w: Dict, tokens: jax.Array, a: Arch, mode: str):
    """The final-normed hidden state and the router margin (the least over
    the layers; None for a dense model) of one sequence."""
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = w["embed"][tokens].astype(_act(mode))

    def body(x, lw):
        return _layer(a, mode, x, lw, pos)

    x, margins = jax.lax.scan(body, x, w["layers"])
    margin = None if margins is None else jnp.min(margins, axis=0)
    return _rms(x, w["final_norm"], a.eps, mode), margin


def hidden(w: Dict, tokens: jax.Array, a: Arch, mode: str = "f32"
           ) -> jax.Array:
    """The final-normed hidden state (S, d) of one sequence ``tokens`` (S,)
    under the weights ``w`` of :mod:`bench.weights` (``embed``,
    ``final_norm``, ``lm_head`` when untied, and ``layers``: each layer's
    tensors stacked on a leading axis)."""
    return _forward(w, tokens, a, mode)[0]


def head(w: Dict, a: Arch, mode: str = "f32") -> jax.Array:
    """The LM head (d, V) as the mode multiplies it."""
    return _w(w["embed"].T if a.tied else w["lm_head"], mode)


@partial(jax.jit, static_argnames=("a", "mode"))
def scores(w: Dict, tokens: jax.Array, targets: jax.Array,
           *, a: Arch, mode: str = "f32"):
    """Run one sequence ``tokens`` (S,) through the model.  Returns, at each
    position, the best logit, the logit of ``targets`` (S,), the argmax and
    the router margin (None for a dense model).  S must be a multiple of
    ``ROW_BLOCK``; causal attention makes padding at the end harmless to the
    positions before it."""
    S = tokens.shape[0]
    x, margin = _forward(w, tokens, a, mode)
    hd = head(w, a, mode)

    def block(args):
        xb, tb = args
        lg = jnp.matmul(xb, hd, precision=HIGHEST,
                        preferred_element_type=jnp.float32)
        best = jnp.max(lg, -1)
        tgt = jnp.take_along_axis(lg, tb[:, None], -1)[:, 0]
        return best, tgt, jnp.argmax(lg, -1).astype(jnp.int32)

    nb = S // ROW_BLOCK
    best, tgt, arg = jax.lax.map(
        block, (x.reshape(nb, ROW_BLOCK, -1), targets.reshape(nb, ROW_BLOCK)))
    return best.reshape(S), tgt.reshape(S), arg.reshape(S), margin
