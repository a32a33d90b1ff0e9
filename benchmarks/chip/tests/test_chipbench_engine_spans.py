"""The readers of the engine's host spans, ``first_token_ms.serve`` and
``decode_host_ms.serve``, on a trace made by hand: two batches, each a
host span ``engine.generate`` over its decode steps, and the device
programs they issued; and a trace without the engine's spans, as a
program without them records."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
from devtrace import Event, Trace  # noqa: E402

DECODE = "jit(scan)/vmemkernel_decode_attention/dot"
PREFILL = "jit(scan)/vmemkernel_flash_attention/dot"

# (name, start, end, framework path of its one operation)
PROGRAMS = [
    # batch 1: prefill, cache growth, first sample, then two decode steps
    # each followed by its sample
    ("jit_scan(1)", 0.30, 0.50, PREFILL),
    ("jit_pad(2)", 0.55, 0.60, "jit(pad)/pad"),
    ("jit_argmax(3)", 0.60, 0.62, "jit(argmax)/argmax"),
    ("jit_scan(4)", 1.20, 1.25, DECODE),
    ("jit_argmax(3)", 1.26, 1.27, "jit(argmax)/argmax"),
    ("jit_scan(4)", 1.90, 1.95, DECODE),
    ("jit_argmax(3)", 1.96, 1.97, "jit(argmax)/argmax"),
    # batch 2: prefill, first sample, one decode step
    ("jit_scan(1)", 5.20, 5.40, PREFILL),
    ("jit_argmax(3)", 5.40, 5.50, "jit(argmax)/argmax"),
    ("jit_scan(4)", 6.00, 6.10, DECODE),
    ("jit_argmax(3)", 6.12, 6.13, "jit(argmax)/argmax"),
]
ENGINE_SPANS = [
    Event("engine.generate", 0.0, 2.5),
    Event("engine.prefill", 0.0, 0.45),
    Event("engine.decode_step", 0.7, 1.3),
    Event("engine.decode_step", 1.3, 2.0),
    Event("engine.generate", 5.0, 6.5),
    Event("engine.prefill", 5.0, 5.3),
    Event("engine.decode_step", 5.6, 6.2),
]


def _trace(engine_spans: bool) -> Trace:
    programs = [Event(n, s, e) for n, s, e, _ in PROGRAMS]
    ops = [Event(f"op.{i}", s, e, path, path)
           for i, (_, s, e, path) in enumerate(PROGRAMS)]
    host = [Event("bench:window", 0.0, 7.0),
            Event("bench:generate", 0.0, 2.5),
            Event("bench:generate", 5.0, 6.5)]
    return Trace([{"ops": ops, "programs": programs}],
                 host + (ENGINE_SPANS if engine_spans else []))


def _read(metric: str, trace: Trace):
    reader = harness.load_module(BENCH / "metrics" / f"{metric}.py")
    ctx = harness.Context(config={}, traffic={},
                          run=harness.Run({}, 0, 0, {}, 0), window_s=7.0,
                          compiles=0, peaks=None, trace=trace)
    return reader.read(ctx)


def test_first_token_is_generate_start_to_the_program_before_decode():
    # batch 1: the first sample's program ends at 0.62 s, 0.62 s after
    # the batch began; batch 2: 5.50 s, 0.50 s after
    assert _read("first_token_ms.serve", _trace(True)) == pytest.approx(
        1e3 * (0.62 + 0.50) / 2)


def test_decode_host_time_is_the_mean_decode_step_span():
    assert _read("decode_host_ms.serve", _trace(True)) == pytest.approx(
        1e3 * (0.6 + 0.7 + 0.6) / 3)


@pytest.mark.parametrize("metric", ["first_token_ms.serve",
                                    "decode_host_ms.serve"])
def test_a_trace_without_engine_spans_reads_nothing(metric):
    assert _read(metric, _trace(False)) is None
