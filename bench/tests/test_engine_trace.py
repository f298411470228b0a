"""The reduction of the program's ``engine.*`` spans on the device trace's
clock: pairing, the offset's bracket, the idle split by innermost span and
the readers of the five span and stamp metrics, on hand-made planes; the
CPU run of ``span_run.py`` at tiny widths."""
from dataclasses import dataclass, field
from typing import Dict, List

import pytest

from bench import engine_trace as et
from bench import harness
from bench import span_run
from bench import spec
from bench import trace_reduce as tr
from bench.tests import test_trace_reduce as base
from bench.tests import tiny

DATA = base.DATA


@dataclass
class Ev:
    name: str
    start_ns: float
    end_ns: float
    stats: Dict[str, object] = field(default_factory=dict)


@dataclass
class Line:
    name: str
    events: List[Ev] = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)


def _planes(late_b=False):
    """Two engine steps; the device's clock reads 150 ns early, so the
    bracket is [50, 250].  ``late_b`` puts step B's execution past its sync
    on the host, which empties the bracket."""
    host = Plane("/host:CPU", [Line("python3", [
        Ev("window", 0, 10_000),
        Ev("engine.step", 1000, 4000, {"step": 0}),
        Ev("engine.prefill", 1100, 2000,
           {"rid": 1, "prompt": 8, "matched": 4, "tokens": 4}),
        Ev("engine.decode", 2100, 3600, {"step": 0, "live": 1}),
        Ev("engine.decode.dispatch", 2200, 2400),
        Ev("engine.decode.sync", 2500, 3500),
        Ev("engine.retire", 3650, 3900, {"retired": 0}),
        Ev("engine.step", 5000, 8000, {"step": 1}),
        Ev("engine.decode", 5100, 7000, {"step": 1, "live": 1}),
        Ev("engine.decode.dispatch", 5200, 5300),
        Ev("engine.decode.sync", 5400, 6900),
        Ev("engine.retire", 7100, 7500, {"retired": 1}),
        Ev("engine.step", 9000, 11_000, {"step": 2})])])   # past the window
    b = (5400, 6950) if late_b else (5200, 6550)
    dev = Plane("/device:TPU:0", [
        Line("XLA Ops", [Ev("fusion.1", 1150, 1650),
                         Ev("paged_flash_decode.1", 2150, 3250),
                         Ev("paged_flash_decode.1", *b)]),
        Line("XLA Modules", [Ev("jit__pgexec", 1150, 1650),
                             Ev("jit__pgexec", 2150, 3250),
                             Ev("jit__pgexec", *b)])])
    return [host, dev]


def test_spans_inside_the_window_are_kept_with_their_stats():
    r = et.reduce_planes(_planes())
    assert r.window == (0, 10_000)
    assert [s.stats["step"] for s in r.named("engine.step")] == [0, 1]
    assert r.named("engine.prefill")[0].stats == {
        "rid": 1, "prompt": 8, "matched": 4, "tokens": 4}
    assert len(r.spans) == 11


def test_pairing_and_bracket():
    r = et.reduce_planes(_planes())
    assert et.decode_steps(r.spans) == [(2200, 3500), (5200, 6900)]
    # A: dispatch 2200 - start 2150 = 50, sync 3500 - end 3250 = 250
    # B: 5200 - 5200 = 0, 6900 - 6550 = 350
    assert r.paired == 2
    assert (r.lo, r.hi) == (50, 250)
    assert r.aligned


def test_pairing_takes_the_most_overlap_and_skips_no_overlap():
    mods = [(0, 120), (130, 400), (900, 950)]
    # (100, 300) overlaps the first by 20 and the second by 170
    assert et.pair([(100, 300), (500, 800)], mods) == [
        ((100, 300), (130, 400))]
    assert et.span_bracket([]) == (None, None)


def test_empty_bracket_shifts_nothing_and_reads_nothing():
    r = et.reduce_planes(_planes(late_b=True))
    # B: 5200 - 5400 = -200, 6900 - 6950 = -50; A as before
    assert (r.lo, r.hi) == (50, -50)
    assert not r.aligned and r.delta is None and r.idle_by_span() is None
    rec = {"engine_trace": r}
    assert _reader("step_host_idle_ms")(rec) is None
    # readers that need no shift still read
    assert _reader("decode_span_ms")(rec) == pytest.approx(0.0017)


@pytest.mark.parametrize("b,delta", [
    ((50, 250), 50), ((-30, 80), 0), ((-90, -20), -20),
    ((-30, 80, 10, 40), 10), ((-30, 80, -50, -40), None)],
    ids=["early", "holds_zero", "late", "runtime_narrows", "disjoint"])
def test_shift_is_the_least_in_the_bracket(b, delta):
    assert et.EngineTrace((0, 1), [], 1, *b).delta == delta


def test_runtime_events_narrow_the_bracket():
    planes = _planes()
    # enqueued 150 / 100 ns after each execution's start on the device's
    # clock, done 200 / 150 ns after its end
    planes[0].lines.append(Line("tfrt", [
        Ev(et.ENQUEUE, 2300, 2320), Ev(et.DONE, 3450, 3460),
        Ev(et.ENQUEUE, 5300, 5320), Ev(et.DONE, 6700, 6710)]))
    r = et.reduce_planes(planes)
    assert (r.lo, r.hi, r.lo_rt, r.hi_rt) == (50, 250, 150, 150)
    assert r.bracket == (150, 150) and r.delta == 150


def test_idle_is_charged_to_the_innermost_span():
    r = et.reduce_planes(_planes())
    # shifted by 50, busy 1200-1700, 2200-3300, 5250-6600; idle inside
    # step A: 1000-1200, 1700-2200, 3300-4000; step B: 5000-5250, 6600-8000
    want = {"engine.step": 100 + 100 + 50 + 100 + 100 + 100 + 500,
            "engine.prefill": 100 + 300,
            "engine.decode": 100 + 100 + 100 + 100,
            "engine.decode.dispatch": 50,
            "engine.decode.sync": 200 + 300,
            "engine.retire": 250 + 400}
    assert r.delta == 50
    assert r.idle_by_span() == pytest.approx({k: v * 1e-9
                                              for k, v in want.items()})
    assert sum(r.idle_by_span().values()) == pytest.approx(3050e-9)


def test_innermost_leaves_out_spans_outside_a_step():
    spans = [et.Span("engine.prefill", 0, 10),
             et.Span("engine.step", 20, 60),
             et.Span("engine.decode", 30, 50),
             et.Span("engine.decode.sync", 35, 45)]
    assert et.innermost(spans) == [
        ("engine.step", 20, 30), ("engine.decode", 30, 35),
        ("engine.decode.sync", 35, 45), ("engine.decode", 45, 50),
        ("engine.step", 50, 60)]


def test_engine_spans_leave_the_harness_reduction_as_it_was():
    """Every field of ``trace_reduce``'s reduction reads the same with the
    program's spans on the host plane as without them."""
    plain = tr.reduce_planes(base._planes())
    planes = base._planes()
    planes[0].lines[0].events += [Ev("engine.step", 150, 700),
                                  Ev("engine.decode", 200, 650)]
    assert tr.reduce_planes(planes) == plain
    with_spans = tr.reduce_planes(_planes())
    stripped = _planes()
    stripped[0].lines[0].events = stripped[0].lines[0].events[:1]
    assert with_spans == tr.reduce_planes(stripped)


def test_harness_planes_and_recorded_trace_have_no_engine_spans():
    r = et.reduce_planes(base._planes())
    assert r.spans == [] and r.paired == 0 and not r.aligned
    r = et.reduce_file(DATA / "v5e_paged_decode.xplane.pb")
    assert r.spans == [] and r.paired == 0


def _reader(name):
    return spec.metric_reader(name)


def test_span_readers():
    rec = {"engine_trace": et.reduce_planes(_planes())}
    # 900 ns over 4 prefilled tokens
    assert _reader("prefill_span_ms_per_ktok")(rec) == pytest.approx(0.225)
    # median of 1500 and 1900 ns
    assert _reader("decode_span_ms")(rec) == pytest.approx(0.0017)
    # 3050 ns over two steps
    assert _reader("step_host_idle_ms")(rec) == pytest.approx(0.001525)
    for name in ("prefill_span_ms_per_ktok", "decode_span_ms",
                 "step_host_idle_ms"):
        assert _reader(name)({}) is None


def _req(rid, due, prompt, admitted=None, matched=0):
    r = harness.ReqRec(rid, due, prompt, 0, 8)
    r.admitted_at, r.prefix_matched = admitted, matched
    return r


def test_stamp_readers():
    reqs = {1: _req(1, 0.5, 100, 0.6, 64), 2: _req(2, 1.0, 50, 1.4),
            3: _req(3, 1.5, 30), 4: _req(4, 5.0, 20, 5.1, 16)}  # 4: late
    rec = {"t0": 0.0, "t1": 2.0, "reqs": reqs}
    # waits 0.1, 0.4, and 3's 0.5 to the window's close: p90 is 0.5
    assert _reader("admit_wait_p90_s")(rec) == pytest.approx(0.5)
    # admitted in the window: 1 and 2
    assert _reader("prefix_mapped_share")(rec) == pytest.approx(
        100 * 64 / 150)
    bare = {1: harness.ReqRec(1, 0.5, 100, 0, 8)}
    for name in ("admit_wait_p90_s", "prefix_mapped_share"):
        assert _reader(name)({"t0": 0.0, "t1": 2.0, "reqs": bare}) is None


def test_span_run_on_cpu(tmp_path):
    """The stamp readers read beside their harness-clock twins; the span
    readers find no trace to read on a CPU (the harness reduces a trace only
    on a TPU); the harness is left unwrapped."""
    cell = tiny.make_tree(tmp_path)
    wr, rt = harness.window_record, harness.reduce_trace
    r = span_run.run(cell, 2 ** 35 + 7, 2.0, root=tmp_path, require_tpu=False)
    assert (harness.window_record, harness.reduce_trace) == (wr, rt)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert r["correct"] is True
    assert m["admit_wait_p90_s"] >= m["queue_wait_p90_s"]
    assert m["prefix_mapped_share"] == pytest.approx(
        m["prefix_hit_share"], abs=0.5)
    assert not {"prefill_span_ms_per_ktok", "decode_span_ms",
                "step_host_idle_ms"} & set(m)
    assert r["traced_end_to_end"]["output_tokens_per_s"] > 0
    assert "clock_offset" not in r


def test_recorded_v5e_engine_trace():
    """A trace taken on a TPU v5e (``record_engine_trace.py``): a step that
    admits three requests (one mapping 48 prefix tokens, one retiring at
    admission) and decodes, then four decode steps.  The expected numbers
    are summed by hand from the events listed."""
    r = et.reduce_file(DATA / "v5e_engine_steps.xplane.pb")
    assert [s.stats["step"] for s in r.named("engine.step")] == [2, 3, 4, 5, 6]
    assert [(s.stats["rid"], s.stats["matched"], s.stats["tokens"])
            for s in r.named("engine.prefill")] == [
        (10, 48, 2), (11, 0, 37), (12, 0, 3)]
    assert [s.stats["retired"] for s in r.named("engine.retire")] == [
        0, 1, 0, 0, 1]
    # decode modules (device clock): 62,587,039-64,752,729,
    # 66,799,826-68,966,331, 71,064,034-73,227,412, 75,336,061-77,499,738,
    # 79,517,776-81,680,674; dispatch starts 62,980,648, 67,165,008,
    # 71,468,508, 75,608,978, 79,924,537; sync ends 67,059,578, 71,357,648,
    # 75,549,007, 79,857,267, 83,962,047.  Dispatch start - module start:
    # 393,609 / 365,182 / 404,474 / 272,917 / 406,761; sync end - module
    # end: 2,306,849 / 2,391,317 / 2,321,595 / 2,357,529 / 2,281,373
    assert r.paired == 5
    assert (r.lo, r.hi) == (406_761, 2_281_373)
    # runtime events: enqueued 64,014,619, 68,204,799, 72,495,079,
    # 76,721,279, 80,948,948 (- module start: 1,427,580 / 1,404,973 /
    # 1,431,045 / 1,385,218 / 1,431,172); done 66,644,739, 70,968,919,
    # 75,145,429, 79,447,099, 83,615,159 (- module end: 1,892,010 /
    # 2,002,588 / 1,918,017 / 1,947,361 / 1,934,485)
    assert (r.lo_rt, r.hi_rt) == (1_431_172, 1_892_010)
    assert r.delta == 1_431_172            # the device's clock reads early
    # at that shift each module (the prefill chunks': 43,193,552-45,326,492;
    # 47,739,147-49,984,109, 49,995,520-52,142,011, 52,150,842-54,313,667;
    # 56,314,121-58,446,848, 58,455,396-60,615,237) lies inside its prefill
    # or sync span, so each span's idle is its self time less its modules;
    # the ops leave ~0.3 us of each module uncovered, hence ``abs``
    want = {
        # step self, per step: 27,040 + 28,090 + 25,310 + 31,580 + 7,290
        # + 32,540; 15,350 + 3,770 + 23,610; 9,410 + 3,160 + 26,171;
        # 7,360 + 3,780 + 28,880; 8,840 + 3,720 + 29,180
        "engine.step": 315_081,
        # 5,040,059 - 2,132,940; 8,681,770 - 2,244,962 - 2,146,491
        # - 2,162,825; 6,248,790 - 2,132,727 - 2,159,841
        "engine.prefill": 2_907_119 + 2_127_492 + 1_956_222,
        # decode less dispatch and sync: 4,094,670 - 938,370 - 3,128,860 ...
        "engine.decode": 27_440 + 30_540 + 18_900 + 17_761 + 17_210,
        "engine.decode.dispatch": (938_370 + 919_910 + 922_310 + 932_129
                                   + 890_170),
        # sync less module: 3,128,860 - 2,165,690 ...
        "engine.decode.sync": (963_170 + 1_099_985 + 989_021 + 1_147_132
                               + 979_292),
        "engine.retire": 19_390 + 55_120 + 7_340 + 9_500 + 87_880}
    got = r.idle_by_span()
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k] == pytest.approx(v * 1e-9, abs=2.5e-6), k
    # the step reader: idle over the five steps
    assert _reader("step_host_idle_ms")({"engine_trace": r}) == \
        pytest.approx(sum(want.values()) * 1e-6 / 5, abs=1e-3)
