"""The engine's profiler spans and per-request admission counters: a CPU
paged engine serves a few requests, one of which maps a resident prefix,
under ``jax.profiler``; the spans' names, nesting and args are read back
from the trace file."""
import glob
import time

import jax
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serving.engine import SPANS, Engine, Request

SHARED = [1 + (5 * i) % 19 for i in range(20)]      # 5 full pages of 4


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Serve four requests (the last maps SHARED's pages, left by the first)
    under a trace; returns the engine, the states, each state's bracketing
    step times, the index's ``tokens_matched`` change and the trace's
    ``engine.*`` events as (name, start, end, stats), sorted."""
    cfg = get_config("qwen2-1.5b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, n_slots=2, max_seq_len=48, page_size=4)
    eng.submit(Request(rid=0, prompt=SHARED + [30], max_new_tokens=3))
    eng.run_until_drained()                # compiles; leaves SHARED indexed
    matched0 = eng.prefix_index.tokens_matched
    n_fin = len(eng.finished)
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    for rid, prompt, new in ((1, [7, 8, 9, 10, 11], 4), (2, [3, 4, 5], 1),
                             (3, SHARED + [31], 3)):
        eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=new))
    bounds = {}
    while eng.waiting or eng.active:
        before = {st.request.rid for st in eng.active.values()}
        t0 = time.monotonic()
        eng.step()
        t1 = time.monotonic()
        for st in list(eng.active.values()) + eng.finished[n_fin:]:
            if st.request.rid not in before and st.request.rid not in bounds:
                bounds[st.request.rid] = (t0, t1)
    jax.profiler.stop_trace()
    pb = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    events = sorted(((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                     for plane in jax.profiler.ProfileData.from_file(pb).planes
                     if plane.name.startswith("/host:")
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith("engine.")),
                    key=lambda e: (e[1], -e[2]))
    states = {st.request.rid: st for st in eng.finished[n_fin:]}
    return (eng, states, bounds, eng.prefix_index.tokens_matched - matched0,
            events)


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_every_span_is_traced(served):
    _, _, _, _, events = served
    assert {e[0] for e in events} == {f"engine.{n}" for n in SPANS}


def test_spans_nest_inside_their_step(served):
    eng, _, _, _, events = served
    steps = [e for e in events if e[0] == "engine.step"]
    assert [e[3]["step"] for e in steps] == list(
        range(eng.steps - len(steps), eng.steps))
    for e in events:
        if e[0] != "engine.step":
            assert sum(_inside(e, s) for s in steps) == 1, e
    decodes = [e for e in events if e[0] == "engine.decode"]
    for name in ("engine.decode.dispatch", "engine.decode.sync"):
        kids = [e for e in events if e[0] == name]
        assert len(kids) == len(decodes)
        assert all(_inside(k, d) for k, d in zip(kids, decodes))
    for d in decodes:                      # the dispatch, then its sync
        disp, sync = (next(e for e in events if e[0] == n and _inside(e, d))
                      for n in ("engine.decode.dispatch",
                                "engine.decode.sync"))
        assert disp[2] <= sync[1]
    # prefill and retire stand beside decode, never inside it
    for e in events:
        if e[0] in ("engine.prefill", "engine.retire"):
            assert not any(_inside(e, d) for d in decodes)


def test_span_args(served):
    _, states, _, _, events = served
    prefills = {e[3]["rid"]: e[3] for e in events if e[0] == "engine.prefill"}
    assert set(prefills) == {1, 2, 3}
    for rid, a in prefills.items():
        st = states[rid]
        assert a["prompt"] == len(st.request.prompt)
        assert a["matched"] == st.prefix_matched
        assert a["tokens"] == a["prompt"] - a["matched"]
    assert prefills[3]["matched"] == 20 and prefills[1]["matched"] == 0
    decodes = [e[3] for e in events if e[0] == "engine.decode"]
    steps = {e[3]["step"] for e in events if e[0] == "engine.step"}
    assert {a["step"] for a in decodes} <= steps
    assert all(a["live"] >= 1 for a in decodes)
    # rid 2 (one new token) retires at admission; the others after a decode
    retired = sum(e[3]["retired"] for e in events if e[0] == "engine.retire")
    assert retired == len(states) - 1


def test_admission_stamp_and_prefix_count(served):
    _, states, bounds, tokens_matched, _ = served
    for rid, st in states.items():
        t0, t1 = bounds[rid]
        assert t0 <= st.admitted_at <= st.first_token_time <= t1
    assert states[3].prefix_matched == 20
    assert sum(st.prefix_matched for st in states.values()) == tokens_matched


def test_unpaged_engine_counts_no_prefix():
    cfg = get_config("qwen2-1.5b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, n_slots=2, max_seq_len=48, paged=False)
    t0 = time.monotonic()
    eng.submit(Request(rid=0, prompt=SHARED + [30], max_new_tokens=2))
    st = eng.run_until_drained()[0]
    assert st.prefix_matched == 0 and t0 <= st.admitted_at
