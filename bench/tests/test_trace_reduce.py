"""The reduction from a profiler trace to busy time, per-op self time and
idle gaps charged to host spans: on hand-made planes, and on a small trace
recorded on a TPU v5e (``data/``)."""
from dataclasses import dataclass, field
from pathlib import Path
from typing import List

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


@dataclass
class Ev:
    name: str
    start_ns: float
    end_ns: float


@dataclass
class Line:
    name: str
    events: List[Ev] = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)


def _planes():
    host = Plane("/host:CPU", [Line("python3", [
        Ev("window", 100, 1100),
        Ev("submit", 100, 150),
        Ev("engine_step", 150, 700),
        Ev("wait_arrival", 700, 1000)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Ops", [
            Ev("while", 200, 500),          # a loop and the ops of its body
            Ev("fusion.1", 210, 300),
            Ev("fusion.2", 300, 350),
            Ev("_paged_decode_kernel", 400, 480),
            Ev("fusion.1", 600, 650),
            Ev("fusion.3", 1050, 1200)]),   # runs past the window's end
        Line("XLA Modules", [Ev("jit_step", 200, 650)])])
    return [host, dev, Plane("/device:TPU:0 SparseCore 0")]


def test_busy_union_and_idle_share():
    r = tr.reduce_planes(_planes())
    assert r.devices == 1
    assert r.window_s == pytest.approx(1000e-9)
    # busy: 200-500, 600-650, 1050-1100 (clipped) = 400 ns
    assert r.busy_s == pytest.approx(400e-9)
    assert r.idle_share == pytest.approx(0.6)


def test_self_time_of_nested_ops():
    r = tr.reduce_planes(_planes())
    assert r.op_self_s["while"] == pytest.approx((300 - 90 - 50 - 80) * 1e-9)
    assert r.op_self_s["fusion.1"] == pytest.approx(140e-9)
    assert r.op_calls["fusion.1"] == 2
    assert r.kernel(r"paged_decode") == (1, pytest.approx(80e-9))


def test_idle_gaps_charged_to_host_spans():
    r = tr.reduce_planes(_planes())
    # idle: 100-200 (submit 50, engine_step 50), 500-600 (engine_step),
    # 650-1050 (engine_step 50, wait_arrival 300, other 50)
    assert r.idle_by_span["submit"] == pytest.approx(50e-9)
    assert r.idle_by_span["engine_step"] == pytest.approx(200e-9)
    assert r.idle_by_span["wait_arrival"] == pytest.approx(300e-9)
    assert r.idle_by_span["other"] == pytest.approx(50e-9)
    assert r.gaps[0] == ("engine_step", pytest.approx(400e-9))


def test_trace_without_window_is_refused():
    planes = _planes()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        tr.reduce_planes(planes)


def test_recorded_v5e_trace():
    """A trace taken on a TPU v5e: three runs of a jitted step (the fused
    paged decode kernel, then a small matmul) under the harness's spans;
    the expected numbers are summed by hand from the events listed in
    the trace."""
    r = tr.reduce_file(DATA / "v5e_paged_decode.xplane.pb")
    assert r.devices == 1
    assert r.window_s == pytest.approx(20_405_160e-9)
    # op union per run: 13+19991+33+2+1436, 13+20490+32+2+1436,
    # 13+19953+31+3+1435 ns (a 1-2 ns gap between some ops)
    assert r.busy_s == pytest.approx(64_883e-9)
    # the kernel is a custom call to the TPU's custom-call target
    assert r.kernel(r'custom_call_target="tpu_custom_call"') == \
        (3, pytest.approx((19_991 + 20_490 + 19_953) * 1e-9))
    # on this trace the device's clock reads ~1 ms earlier than the host's:
    # every op falls inside a host ``submit`` span, so the ``engine_step``
    # and ``wait_arrival`` spans are idle throughout
    assert r.idle_by_span["engine_step"] == pytest.approx(
        (936_080 + 585_710 + 856_410) * 1e-9)
    assert r.idle_by_span["wait_arrival"] == pytest.approx(
        (3_347_930 + 3_712_250 + 3_436_560) * 1e-9)
    assert r.idle_by_span["submit"] == pytest.approx(
        (2_980_130 + 2_210_200 + 2_229_040 - 64_883) * 1e-9)
    assert sum(r.idle_by_span.values()) == pytest.approx(r.window_s - r.busy_s)
