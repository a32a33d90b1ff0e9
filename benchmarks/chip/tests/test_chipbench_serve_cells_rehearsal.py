"""The serving cells added after ``danube-serve-chat`` rehearsed end to
end on the CPU at the program's reduced sizes, as
``test_chipbench_serve_rehearsal.py`` rehearses that one: each as it is;
the latent-attention MoE cell with its timed path broken, where
``correct`` must come out false; and its control (``control_mla.py``),
which must read above the program and fail the cell's limit. The
parameter lists take each serving cell that later changes add."""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import control_mla  # noqa: E402
import harness  # noqa: E402
from test_chipbench_serve_rehearsal import (SEED, _alter_second_token,  # noqa: E402
                                            _keep_the_cache)

MLA = "moonlight-serve-chat"


def _rehearse(name):
    return harness.run_cell(harness.load_cell(name), SEED, 0.1, False,
                            time.perf_counter(), rehearsal=True)


@pytest.mark.parametrize("name", [MLA])
def test_rehearsal_is_correct(name):
    result = _rehearse(name)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                      "serve_request_p95_s"}
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", [_alter_second_token, _keep_the_cache])
def test_a_broken_latent_cache_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    assert _rehearse(MLA)["correct"] is False


def test_control_reads_above_the_program():
    """By the mean gap: the widest gap of one seed can read the program
    above the control, where bf16 tips a near-tie of the router
    (``control_mla.py --witness``)."""
    cell = harness.load_cell(MLA)
    readings = list(control_mla.serve_readings(cell, [1, SEED],
                                               rehearsal=True))
    program = max(r["program_mean"] for r in readings)
    fp8 = min(r["control_fp8_mean"] for r in readings)
    assert fp8 > 0 and fp8 >= 3 * program, readings
    # judged by the cell's limit, the program passes and the control fails
    assert all(r["program_correct"] for r in readings), readings
    assert not any(r["control_fp8_correct"] for r in readings), readings


def test_witness_places_each_widest_gap(tmp_path):
    """``--witness``: each of the program's widest gaps with the bf16
    reference's gap and the routing margin at its position, every
    position's saved for the seed."""
    cell = harness.load_cell(MLA)
    (r,) = control_mla.serve_readings(cell, [SEED], rehearsal=True,
                                      witness=tmp_path)
    saved = np.load(tmp_path / f"{SEED}.npz")
    assert saved["program"].shape == saved["margins"].shape \
        == saved["control_fp8"].shape
    assert float(saved["margins"].min()) >= 0.0
    assert r["widest"][0][2] == r["program"] and len(r["widest"]) == 5
    for req, step, gap, bf16, margin, below in r["widest"]:
        assert saved["program"][req, step] == gap
        assert saved["bf16"][req, step] == bf16
        assert saved["margins"][req, step] == margin and 0 <= below <= 1
