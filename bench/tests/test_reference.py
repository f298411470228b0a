"""The plain references against the program's paged path, at tiny widths on
the CPU, with the program computing in float32 so the two must agree to
rounding: paged prefill, decode (gather path and the fused kernel in
interpret mode), and a prefill whose first pages are mapped from another
request's, as the prefix index maps them."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.harness import check_config, program_config
from bench.reference import model as ref
from bench.spec import Cell
from bench.tests import tiny

PAGE = 16


def _setup(config):
    cell = Cell("t", config["name"], "t", 1, config, {}, {}, (), ())
    cfg = dataclasses.replace(program_config(cell), dtype="float32")
    a = ref.arch(config)
    check_config(cfg, a)
    w = weights.make(a, 5)
    return cfg, a, w, weights.program_params(a, w)


def _ref_logits(w, a, tokens):
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(w, jnp.asarray(tokens, jnp.int32), a)
        return np.asarray(h @ ref.head(w, a))


def _paged(cfg, params, cache, toks, pos, ptab, use_kernel=False):
    from repro.models import lm
    B = ptab.shape[0]
    tok = np.zeros((B, len(toks)), np.int32)
    ps = np.zeros((B, len(toks)), np.int32)
    tok[0], ps[0] = toks, pos
    act = np.zeros((B,), bool)
    act[0] = True
    with jax.default_matmul_precision("highest"):
        logits, cache = lm.paged_step(params, cfg, cache, tok, ps, ptab, act,
                                      page_size=PAGE, use_kernel=use_kernel,
                                      interpret=True)
    return np.asarray(logits[0]), cache


@pytest.mark.parametrize("config", [tiny.TINY_CONFIG, tiny.TINY_MOE],
                         ids=["dense", "moe"])
@pytest.mark.parametrize("use_kernel", [False, True], ids=["gather", "kernel"])
def test_prefill_and_decode_match_reference(config, use_kernel):
    from repro.models import lm
    cfg, a, w, params = _setup(config)
    rng = np.random.default_rng(0)
    seq = rng.integers(1, a.vocab, 41).tolist()
    want = _ref_logits(w, a, seq)
    cache = lm.init_paged_cache(cfg, 8, PAGE, dtype=jnp.float32)
    ptab = np.zeros((2, 4), np.int32)
    ptab[0, :3] = [1, 2, 3]
    got, cache = _paged(cfg, params, cache, seq[:40], np.arange(40), ptab)
    np.testing.assert_allclose(got, want[:40], atol=2e-4, rtol=2e-4)
    got, _ = _paged(cfg, params, cache, seq[40:], [40], ptab,
                    use_kernel=use_kernel)
    np.testing.assert_allclose(got[0], want[40], atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("config", [tiny.TINY_CONFIG, tiny.TINY_MOE],
                         ids=["dense", "moe"])
def test_prefix_mapped_prefill_matches_reference(config):
    from repro.models import lm
    cfg, a, w, params = _setup(config)
    rng = np.random.default_rng(1)
    first = rng.integers(1, a.vocab, 40).tolist()
    second = first[:32] + rng.integers(1, a.vocab, 12).tolist()
    cache = lm.init_paged_cache(cfg, 8, PAGE, dtype=jnp.float32)
    ptab = np.zeros((2, 4), np.int32)
    ptab[0, :3] = [1, 2, 3]
    _, cache = _paged(cfg, params, cache, first, np.arange(40), ptab)
    # the second request maps the first's two full pages and prefills the
    # rest into a page of its own
    ptab[0, :3] = [1, 2, 4]
    got, _ = _paged(cfg, params, cache, second[32:], np.arange(32, 44), ptab)
    np.testing.assert_allclose(got, _ref_logits(w, a, second)[32:],
                               atol=2e-4, rtol=2e-4)


def test_program_departing_from_the_file_is_refused():
    cfg, a, _, _ = _setup(tiny.TINY_CONFIG)
    with pytest.raises(ValueError):
        check_config(dataclasses.replace(cfg, norm_eps=1e-5), a)
