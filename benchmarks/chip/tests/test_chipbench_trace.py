"""The reduction from a profiler trace to what the per-layer metrics
read: on events made by hand, and on a small trace recorded on a TPU v5e
(``data/micro.xplane.pb``: three runs of a jitted step whose scores and
softmax sit under ``vmemkernel_decode_attention``, each inside a host
span ``bench:step`` and 2 ms apart, all inside ``bench:window``, then
one eager multiply; ``record_micro_trace.py`` records it)."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
from devtrace import Event, Trace  # noqa: E402

RECORDED = BENCH / "tests" / "data" / "micro.xplane.pb"


def test_union_and_gaps_by_hand():
    spans = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert devtrace.union_s(spans) == pytest.approx(3.0)
    assert devtrace.gaps(spans, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                               (4.0, 5.0)]
    assert devtrace.gaps(spans, 0.2, 1.5) == []


DECODE = "jit(f)/vmemkernel_decode_attention/dot"


def _op(name, start, end, path, fused=(), container=False):
    return Event(name, start, end, path, "\n".join((path, *fused)),
                 container)


def _hand_trace():
    ops = [_op("while.1", 1.0, 3.0, "jit(f)/while", container=True),
           _op("fusion.1", 1.0, 2.0, DECODE),
           _op("fusion.2", 2.0, 3.0, "jit(f)/out/dot",
               fused=["jit(f)/vmemkernel_decode_attention/div"]),
           _op("fusion.3", 5.0, 6.0, "jit(p)/vmemkernel_flash_attention/dot"),
           _op("fusion.1", 7.0, 8.0, DECODE),
           _op("fusion.4", 8.0, 8.5, "jit(f)/mlp/dot")]
    programs = [Event("jit_f(1)", 1.0, 3.0), Event("jit_p(2)", 5.0, 6.0),
                Event("jit_f(1)", 7.0, 8.5)]
    host = [Event("bench:window", 0.0, 10.0),
            Event("bench:generate", 0.0, 9.0),
            Event("backend_compile", 3.2, 4.8)]
    return Trace([{"ops": ops, "programs": programs}], host)


def test_hand_trace_reads():
    tr = _hand_trace()
    assert tr.window_s == 10.0
    assert tr.busy_s == pytest.approx(4.5)
    # control flow is left out; a fusion with any of the scope's work in
    # it counts whole
    assert tr.scope_time_s("vmemkernel_decode_attention") == pytest.approx(3.0)
    assert tr.scope_time_s("vmemkernel_flash_attention") == pytest.approx(1.0)
    assert tr.busiest_program_runs() == [(1.0, 3.0), (7.0, 8.5)]
    assert tr.runs_with_scope("vmemkernel_decode_attention") == [
        (1.0, 3.0), (7.0, 8.5)]
    assert tr.runs_with_scope("vmemkernel_flash_attention") == [(5.0, 6.0)]
    assert tr.idle_gaps() == [(0.0, 1.0), (3.0, 5.0), (6.0, 7.0),
                              (8.5, 10.0)]
    b = tr.breakdown(top=2)
    assert b["device_ops"] == [[DECODE, 2.0], ["jit(f)/out/dot", 1.0]]
    # the longest gap is named by the narrowest span that covers it most
    assert b["idle_gaps"] == [["backend_compile", 2.0],
                              ["bench:generate", 1.5]]


@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.exists():
        pytest.fail(f"{RECORDED} is missing")
    return Trace.from_file(RECORDED)


def test_recorded_trace_has_one_chip_and_the_window(recorded):
    assert len(recorded.devices) == 1
    assert 0.010 < recorded.window_s < 0.015
    # four tiny programs: microseconds of device time
    assert 0 < recorded.busy_s < 1e-4
    assert len(recorded.devices[0]["programs"]) == 4


def test_recorded_scope_and_programs(recorded):
    runs = recorded.runs_with_scope("vmemkernel_decode_attention")
    assert len(runs) == 3
    assert recorded.busiest_program_runs() == runs
    starts = [s for s, _ in runs]
    # host spans sit 2 ms or more apart: so do the programs they launched
    assert all(b - a >= 0.002 for a, b in zip(starts, starts[1:]))
    t = recorded.scope_time_s("vmemkernel_decode_attention")
    assert 0 < t <= sum(e - s for s, e in runs)
    ops = recorded.devices[0]["ops"]
    scores = [e for e in ops if e.path.endswith("bd,sd->bs/dot_general")]
    assert len(scores) == 3
    # the eager multiply after the window's steps is under no scope
    assert recorded.scope_time_s("vmemkernel_flash_attention") == 0.0


def test_recorded_gaps_are_named_by_the_host(recorded):
    b = recorded.breakdown()
    assert b["device_ops"][0][0].startswith("jit(step)/")
    gaps = b["idle_gaps"]
    assert [n for n, _ in gaps[:3]] == ["bench:step"] * 3
    assert 0.002 < gaps[0][1] < 0.005
    assert sum(s for _, s in gaps) <= recorded.window_s
