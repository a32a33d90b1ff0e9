"""The training cell rehearsed end to end on the CPU at the program's
reduced sizes: once as it is, then with the timed path broken underneath
in each way a one-chip training cell can break, where ``correct`` must
come out false; and the control, which must read above the program and
fail the cell's limits. The cell is not yet in ``BENCHMARK.json``: its
entries wait in ``pending/``, and each test adds them to a copy."""

import json
import sys
import time
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import harness  # noqa: E402
from repro.launch import train as train_mod  # noqa: E402

CELL = "danube-train-pretrain"


def _cell(tmp_path):
    """The cell, loaded from a copy of BENCHMARK.json with its pending
    entries added."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    pending = json.loads((BENCH / "pending" / f"{CELL}.json").read_text())
    for key, entries in pending.items():
        bench[key] += entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "benchmarks").symlink_to(ROOT / "benchmarks")
    return harness.load_cell(CELL, root=tmp_path)


def _rehearse(cell):
    return harness.run_cell(cell, 2**31 + 13, 0.1, False,
                            time.perf_counter(), rehearsal=True)


def test_rehearsal_is_correct(tmp_path):
    result = _rehearse(_cell(tmp_path))
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "train_tokens_per_s",
                                      "ckpt_save_s"}
    # the program's readings at this size, well under the control's below
    assert max(c["value"] for c in result["checks"].values()) < 0.01


def _state_unchanged(monkeypatch):
    step = train_mod.train_step

    def unchanged(state, batch, cfg, opt_cfg):
        _, metrics = step(jax.tree.map(lambda x: x + 0, state), batch,
                          cfg, opt_cfg)
        return state, metrics
    monkeypatch.setattr(train_mod, "train_step", unchanged)


def _half_the_batch(monkeypatch):
    step = train_mod.train_step

    def half(state, batch, cfg, opt_cfg):
        return step(state, {k: v[: v.shape[0] // 2] for k, v in
                            batch.items()}, cfg, opt_cfg)
    monkeypatch.setattr(train_mod, "train_step", half)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_batch])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, tmp_path):
    fault(monkeypatch)
    result = _rehearse(_cell(tmp_path))
    assert result["correct"] is False, result["checks"]


def test_control_reads_above_the_program(tmp_path):
    cell = _cell(tmp_path)
    program = {k: c["value"] for k, c in _rehearse(cell)["checks"].items()}
    readings = {r["reading"]: r for r in
                control.train_readings(cell, 0.1, rehearsal=True)}
    fp8 = readings["control_fp8"]
    assert any(fp8[k] >= 3 * program[k] for k in
               ("loss_gap", "first_grad_gap", "change_gap")), (fp8, program)
    # put in the program's place, the control fails the cell's own limits
    assert fp8["correct"] is False, fp8
