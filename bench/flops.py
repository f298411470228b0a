"""Operations and bytes from a configuration's shapes.

Only the work that the model's definition asks for counts: the experts that
routing chooses (top-k of E), attention over the positions a query may see,
the LM head only where its logits are used, and the K/V pages that hold
live positions.  Whatever the program computes beyond that (rows of idle
slots, experts not chosen, logits thrown away) is not useful work and is
left out, so a share of a peak built on these counts cannot pass 100%.

A multiply-add is two operations.  ``a`` is a :class:`bench.reference.model.Arch`.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def linear_flops_per_token(a) -> int:
    """The matrix products of every layer for one token: Q/K/V/O
    projections, and the SwiGLU feed-forward, or the router and top-k of
    the experts."""
    qkv_o = 2 * a.d * (a.heads + 2 * a.kv_heads) * a.head_dim \
        + 2 * a.heads * a.head_dim * a.d
    if a.experts:
        ffn = 2 * a.d * a.experts + a.top_k * 2 * 3 * a.d * a.ffn
    else:
        ffn = 2 * 3 * a.d * a.ffn
    return a.layers * (qkv_o + ffn)


def head_flops(a) -> int:
    """The LM head for one position."""
    return 2 * a.d * a.vocab


def attention_flops(a, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys, in
    every layer."""
    return a.layers * 4 * a.heads * a.head_dim * context


def prefill_flops(a, start: int, n: int) -> int:
    """A prompt's positions ``start .. start+n-1`` (the ones before
    ``start`` came from the prefix index), causal, with the head at the
    last position only."""
    contexts = n * start + n * (n + 1) // 2      # Σ (p + 1) over the chunk
    return n * linear_flops_per_token(a) + attention_flops(a, 1) * contexts \
        + head_flops(a)


def decode_flops(a, context: int) -> int:
    """One decoded token whose query sees ``context`` positions."""
    return linear_flops_per_token(a) + attention_flops(a, context) \
        + head_flops(a)


def paged_decode_call(a, lens: Iterable[int], page: int, batch: int,
                      kv_bytes: int = 2, act_bytes: int = 2
                      ) -> Tuple[int, int]:
    """Operations and bytes of one call of the paged decode kernel (one
    layer): ``lens`` are the live slots' context lengths.  Bytes are the
    K and V pages that hold live positions, whole pages, plus the queries
    and outputs of all ``batch`` rows."""
    lens = [int(n) for n in lens]
    flops = sum(4 * a.heads * a.head_dim * n for n in lens)
    pages = sum(-(-n // page) for n in lens)
    kv = pages * page * a.kv_heads * a.head_dim * 2 * kv_bytes
    qo = 2 * batch * a.heads * a.head_dim * act_bytes
    return flops, kv + qo


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> float:
    """Roofline: the larger of compute time and memory time."""
    return max(flops / peak_flops, nbytes / peak_bw)
