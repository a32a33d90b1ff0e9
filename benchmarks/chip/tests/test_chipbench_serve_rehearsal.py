"""The serving cell rehearsed end to end on the CPU at the program's
reduced sizes: once as it is, then with the timed path broken underneath
in each way a serving cell can break, where ``correct`` must come out
false; and the control, which must read above the program and fail the
cell's limit."""

import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import harness  # noqa: E402
from repro.serve import engine as engine_mod  # noqa: E402

CELL = "danube-serve-chat"
SEED = 2**31 + 11


def _rehearse():
    cell = harness.load_cell(CELL)
    return harness.run_cell(cell, SEED, 0.1, False, time.perf_counter(),
                            rehearsal=True)


def test_rehearsal_is_correct():
    result = _rehearse()
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                      "serve_request_p95_s"}
    assert result["device"]["platform"] == "cpu"


def _alter_second_token(monkeypatch):
    sample = engine_mod.Engine._sample
    calls = []

    def altered(self, logits, key):
        tok = sample(self, logits, key)
        calls.append(1)
        second = len(calls) % self.scfg.max_new_tokens == 2
        return (tok + 1) % self.cfg.vocab_size if second else tok
    monkeypatch.setattr(engine_mod.Engine, "_sample", altered)


def _keep_the_cache(monkeypatch):
    step = engine_mod.decode_step

    def unchanged(params, cfg, tokens, caches, pos):
        logits, _ = step(params, cfg, tokens, caches, pos)
        return logits, caches
    monkeypatch.setattr(engine_mod, "decode_step", unchanged)


def _drop_half_the_batch(monkeypatch):
    generate = engine_mod.Engine.generate

    def half(self, tokens, *args, **kwargs):
        return generate(self, tokens[: tokens.shape[0] // 2], *args, **kwargs)
    monkeypatch.setattr(engine_mod.Engine, "generate", half)


@pytest.mark.parametrize("fault", [_alter_second_token, _keep_the_cache,
                                   _drop_half_the_batch])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    assert _rehearse()["correct"] is False


def test_control_reads_above_the_program():
    cell = harness.load_cell(CELL)
    readings = list(control.serve_readings(cell, [1, SEED],
                                           rehearsal=True))
    program = max(r["program"] for r in readings)
    fp8 = max(r["control_fp8"] for r in readings)
    assert fp8 > 0 and fp8 >= 3 * program, readings
    # judged by the cell's limit, the program passes and the control fails
    assert all(r["program_correct"] for r in readings), readings
    assert not any(r["control_fp8_correct"] for r in readings), readings
