"""A broken timed path makes ``correct`` false.  The harness is driven on the
CPU at tiny widths with its look for a chip skipped, and the program's
paged step is broken underneath, on a dense model and on a mixture of
experts (judged with its router ties left out), once for each fault a
one-chip serving cell can have:

- the step returns the K/V pool it was given, unchanged;
- half of the batch is left out: every other lane is treated as idle, so
  its K/V writes go to the trash page;
- a token is altered where it is produced: the logits of every active lane
  favour one fixed token.

A one-chip cell exchanges nothing between chips, so that fault has no
case here."""
import time

import jax.numpy as jnp
import pytest

from bench import harness, spec
from bench.tests import tiny

SEED = 2 ** 34 + 5


def _state_unchanged(orig):
    def step(params, cfg, cache, *a, **k):
        logits, _ = orig(params, cfg, cache, *a, **k)
        return logits, cache
    return step


def _half_batch(orig):
    def step(params, cfg, cache, tokens, pos2, ptab, active, **k):
        keep = jnp.arange(active.shape[0]) % 2 == 0
        return orig(params, cfg, cache, tokens, pos2, ptab,
                    jnp.logical_and(active, keep), **k)
    return step


def _token_altered(orig):
    def step(params, cfg, cache, *a, **k):
        logits, c2 = orig(params, cfg, cache, *a, **k)
        return logits.at[..., 7].add(1e3), c2
    return step


# load enough that every slot serves requests, so a fault in any lane shows
BUSY = dict(tiny.TINY_MIX, arrival={"process": "poisson", "rate_per_s": 16.0},
            output={"dist": "uniform", "min": 8, "max": 24})


MOE_LIMITS = dict(tiny.TINY_LIMITS, router_tie_margin=0.021,
                  max_router_tie_share=0.5)
FAULTS = pytest.mark.parametrize(
    "fault", [None, _state_unchanged, _half_batch, _token_altered],
    ids=["sound", "state_unchanged", "half_batch", "token_altered"])


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    d = tmp_path_factory.mktemp("faults")
    return d, spec.load_cell(tiny.make_tree(d, mix=BUSY), d)


@pytest.fixture(scope="module")
def moe_cell(tmp_path_factory):
    d = tmp_path_factory.mktemp("faults-moe")
    return d, spec.load_cell(tiny.make_tree(d, config=tiny.TINY_MOE, mix=BUSY,
                                            limits=MOE_LIMITS), d)


def _run_with(cell, fault, monkeypatch):
    from repro.models import lm
    if fault is not None:
        monkeypatch.setattr(lm, "paged_step", fault(lm.paged_step))
    d, c = cell
    return harness.run(c, SEED, 2.0, False, time.monotonic(),
                       require_tpu=False, root=d)


@FAULTS
def test_fault_makes_correct_false(cell, fault, monkeypatch):
    r = _run_with(cell, fault, monkeypatch)
    assert r["correct"] is (fault is None), r["checks"]


@FAULTS
def test_fault_makes_correct_false_moe(moe_cell, fault, monkeypatch):
    r = _run_with(moe_cell, fault, monkeypatch)
    assert "router_tie_share" in r["checks"]
    assert r["correct"] is (fault is None), r["checks"]
