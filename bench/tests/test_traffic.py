"""The seeded generator: the same seed gives the same schedule, another seed
the same work in another order; open and closed loops; Zipf prefixes."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"
TEST_MIXES = Path(__file__).resolve().parent / "mixes"
SEED = 2 ** 33 + 12345            # past 32 bits, as the driver's seeds are


def _mix(name):
    path = MIXES / f"{name}.json"
    if not path.is_file():            # a mix no cell uses yet
        path = TEST_MIXES / f"{name}.json"
    return json.loads(path.read_text())


def _sig(reqs):
    return [(r.idx, r.prompt_len, r.output_len, r.prefix, r.due) for r in reqs]


@pytest.mark.parametrize("name", ["chat", "shared-prefix"])
def test_open_loop_same_seed_same_requests(name):
    mix = _mix(name)
    a = traffic.open_schedule(mix, 30)
    b = traffic.open_schedule(mix, 30)
    assert _sig(a) == _sig(b)
    pa = traffic.prefix_tokens(mix, SEED, 1000)
    assert traffic.prompt(a[3], SEED, 1000, pa) == traffic.prompt(
        b[3], SEED, 1000, traffic.prefix_tokens(mix, SEED, 1000))


@pytest.mark.parametrize("name", ["chat", "shared-prefix"])
def test_other_seed_same_work_other_tokens(name):
    """The seed draws token ids (and weights), never lengths, prefixes or
    arrival times: every seed offers the same load."""
    mix = _mix(name)
    s = traffic.open_schedule(mix, 30)
    pa = traffic.prefix_tokens(mix, SEED, 5000)
    pb = traffic.prefix_tokens(mix, SEED + 1, 5000)
    a = traffic.prompt(s[5], SEED, 5000, pa)
    b = traffic.prompt(s[5], SEED + 1, 5000, pb)
    assert len(a) == len(b) and a != b


def test_open_loop_rate_and_span():
    mix = _mix("chat")
    rate, secs = mix["arrival"]["rate_per_s"], 30
    s = traffic.open_schedule(mix, secs)
    window = [r for r in s if r.due >= 0]
    pre = [r for r in s if r.due < 0]
    assert len(window) == round(rate * secs)
    assert len(pre) == round(rate * mix["preroll_s"])
    assert all(0 <= r.due < secs for r in window)
    assert all(-mix["preroll_s"] <= r.due < 0 for r in pre)
    assert [r.due for r in s] == sorted(r.due for r in s)
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    assert all(lo <= r.prompt_len <= hi for r in s)
    med = np.median([r.prompt_len for r in window])
    assert abs(med - mix["prompt"]["median"]) / mix["prompt"]["median"] < 0.1


def test_closed_loop_blocks_hold_the_same_work():
    mix = _mix("long-prompt")
    a = traffic.closed_pool(mix, 3 * traffic.CLOSED_BLOCK)
    assert _sig(a) == _sig(traffic.closed_pool(mix, len(a)))
    assert all(r.due is None for r in a)
    n = traffic.CLOSED_BLOCK
    for k in range(1, 3):
        assert sorted(r.prompt_len for r in a[k * n:(k + 1) * n]) == \
            sorted(r.prompt_len for r in a[:n])
        assert [r.prompt_len for r in a[k * n:(k + 1) * n]] != \
            [r.prompt_len for r in a[:n]]
    assert all(16 <= r.output_len <= 64 for r in a)
    with pytest.raises(ValueError):
        traffic.open_schedule(mix, 10)


def test_zipf_prefix_popularity():
    c = traffic.zipf_counts(8, 1.0, 1000)
    assert c.sum() == 1000
    assert list(c) == sorted(c, reverse=True)
    w = 1 / np.arange(1, 9)
    np.testing.assert_allclose(c / 1000, w / w.sum(), atol=1e-3)
    mix = _mix("shared-prefix")
    s = [r for r in traffic.open_schedule(mix, 30) if r.due >= 0]
    got = Counter(r.prefix for r in s)
    assert got == Counter(dict(enumerate(traffic.zipf_counts(8, 1.0, len(s)))))
    pre = traffic.prefix_tokens(mix, SEED, 5000)
    p = traffic.prompt(s[0], SEED, 5000, pre)
    assert p[:1024] == pre[s[0].prefix].tolist()
    assert len(p) == 1024 + s[0].prompt_len
    assert all(1 <= t < 5000 for t in p)
