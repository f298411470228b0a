"""The logit check and router ties, at tiny widths on the CPU: a dense model's
gaps and verdict are those of the check without ties; a planted near-tie of
a mixture's router is marked as a tie at exactly its position; a tie share
over its limit reads ``correct`` false."""
import copy
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec, weights
from bench.reference import model as ref
from bench.tests import tiny

DELTA = 0.021
TIE_LIMITS = dict(tiny.TINY_LIMITS, router_tie_margin=DELTA,
                  max_router_tie_share=0.5)


@partial(jax.jit, static_argnames=("a",))
def _scores_without_margins(w, tokens, targets, *, a):
    """``ref.scores`` as it was before it returned router margins."""
    S = tokens.shape[0]
    x = ref.hidden(w, tokens, a)
    hd = ref.head(w, a)

    def block(args):
        xb, tb = args
        lg = jnp.matmul(xb, hd, precision=ref.HIGHEST,
                        preferred_element_type=jnp.float32)
        return jnp.max(lg, -1), jnp.take_along_axis(lg, tb[:, None], -1)[:, 0]

    nb = S // ref.ROW_BLOCK
    best, tgt = jax.lax.map(block, (x.reshape(nb, ref.ROW_BLOCK, -1),
                                    targets.reshape(nb, ref.ROW_BLOCK)))
    return best.reshape(S), tgt.reshape(S)


def _seqs(a, lengths, seed=4):
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, a.vocab, n).tolist(),
             rng.integers(1, a.vocab, 12).tolist()) for n in lengths]


def test_dense_gaps_and_verdict_unchanged_without_tie_keys():
    a = ref.arch(tiny.TINY_CONFIG)
    w = weights.make(a, 5)
    seqs = _seqs(a, (20, 261))
    gaps, margins = harness.reference_gaps(w, a, seqs)
    for (prompt, served), g, m in zip(seqs, gaps, margins):
        seq = prompt + served
        S = harness.bucket(len(seq))
        tok, tgt = np.zeros(S, np.int32), np.zeros(S, np.int32)
        tok[:len(seq)], tgt[:len(seq) - 1] = seq, seq[1:]
        with jax.default_matmul_precision("highest"):
            best, got = _scores_without_margins(
                w, jnp.asarray(tok), jnp.asarray(tgt), a=a)
        lo, hi = len(prompt) - 1, len(seq) - 1
        np.testing.assert_array_equal(g, np.asarray(best - got)[lo:hi])
        assert np.isinf(m).all() and len(m) == len(g)
    verdict = harness.judge(gaps, tiny.TINY_LIMITS, margins)
    assert verdict == harness.judge(gaps, tiny.TINY_LIMITS)
    g = np.concatenate(gaps)
    assert verdict == {
        "served_tokens_checked": {"value": len(g), "limit": 16,
                                  "ok": len(g) >= 16},
        "max_logit_gap": {"value": float(g.max()), "limit": 0.05,
                          "ok": float(g.max()) <= 0.05}}
    # a dense model has no ties, whatever the margin
    assert harness.judge(gaps, TIE_LIMITS, margins)["router_tie_share"][
        "value"] == 0.0


def test_planted_near_tie_is_marked_at_its_position():
    """One MoE layer whose router logits are ``c_e * t_s`` with c = (3, 2, 1,
    0): the 2nd and 3rd largest differ by |t_s| at position s.  The router's
    direction is chosen orthogonal to position p's router input, plus
    DELTA / 4 along it, so |t_p| = DELTA / 4 and every other |t_s| is of order
    ten: p alone is a tie."""
    one = copy.deepcopy(tiny.TINY_MOE)
    one["num_hidden_layers"] = 1
    a = ref.arch(one)
    w = weights.make(a, 9)
    (prompt, served), = _seqs(a, (20,))
    seq = prompt + served
    lo = len(prompt) - 1
    p = lo + 5
    S = harness.bucket(len(seq))
    tok = jnp.asarray(np.pad(seq, (0, S - len(seq))), jnp.int32)
    lw = jax.tree.map(lambda x: x[0], w["layers"])
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tok]
        pos = jnp.arange(S, dtype=jnp.int32)
        x = x + ref._attention(a, lw, ref._rms(x, lw["attn_norm"], a.eps,
                                               "f32"), pos, "f32")
        h = np.asarray(ref._rms(x, lw["mlp_norm"], a.eps, "f32"))[p]
    v = np.random.default_rng(0).standard_normal(a.d)
    v -= v @ h / (h @ h) * h
    v = v / np.linalg.norm(v) * 10 / np.sqrt(a.d) + DELTA / 4 * h / (h @ h)
    router = np.outer(v, [3.0, 2.0, 1.0, 0.0]).astype(np.float32)
    w["layers"]["router"] = jnp.asarray(router)[None]
    gaps, margins = harness.reference_gaps(w, a, [(prompt, served)])
    m = margins[0]
    assert np.flatnonzero(m < DELTA).tolist() == [p - lo]
    assert abs(m[p - lo] - DELTA / 4) < 1e-4
    verdict = harness.judge(gaps, TIE_LIMITS, margins)
    assert verdict["router_tie_share"]["value"] == 1 / len(served)
    assert verdict["served_tokens_checked"]["value"] == len(served) - 1
    assert verdict["max_logit_gap"]["value"] == float(
        np.delete(gaps[0], p - lo).max())


def test_tie_share_over_its_limit_fails_the_check():
    gaps = [np.zeros(10, np.float32)]
    margins = [np.array([0.001] * 6 + [1.0] * 4, np.float32)]
    verdict = harness.judge(gaps, TIE_LIMITS, margins)
    assert verdict["router_tie_share"] == {"value": 0.6, "limit": 0.5,
                                           "ok": False}
    assert verdict["served_tokens_checked"]["value"] == 4


@pytest.fixture(scope="module")
def moe_tree(tmp_path_factory):
    d = tmp_path_factory.mktemp("ties")
    limits = dict(TIE_LIMITS, max_router_tie_share=0.0)
    return d, spec.load_cell(tiny.make_tree(d, config=tiny.TINY_MOE,
                                            limits=limits), d)


def test_tie_share_over_its_limit_reads_correct_false(moe_tree):
    d, c = moe_tree
    r = harness.run(c, 2 ** 33 + 17, 2.0, False, time.monotonic(),
                    require_tpu=False, root=d)
    share = r["checks"]["router_tie_share"]
    assert share["value"] > share["limit"] == 0.0
    assert r["checks"]["max_logit_gap"]["value"] <= 0.05
    assert r["correct"] is False
