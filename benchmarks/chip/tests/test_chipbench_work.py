"""The benchmark's work counts against hand counts at small shapes, and
its table of peaks."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import work  # noqa: E402
from peaks import PEAKS, Peaks, UnknownDevice, peaks_for  # noqa: E402

# 2 layers, d 8, 4 query heads and 2 KV heads of size 2, d_ff 16, vocab 10
CFG = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
       "head_dim": 2, "d_ff": 16, "vocab_size": 10, "sliding_window": 3,
       "param_dtype": "bfloat16"}


def test_attended_pairs_by_hand():
    assert work.attended_pairs(5, None) == 1 + 2 + 3 + 4 + 5
    assert work.attended_pairs(5, 3) == 1 + 2 + 3 + 3 + 3
    assert work.attended_pairs(2, 3) == 1 + 2


def test_layer_params_by_hand():
    # q and o: 8 x 8 each; k and v: 8 x 4 each; MLP: 3 x 8 x 16
    assert work.layer_matmul_params(CFG) == 64 + 64 + 32 + 32 + 384


@pytest.mark.parametrize("measured, expected", [(0.0, None), (16.0, 50.0)])
def test_prefill_roofline_is_least_time_over_scope_time(measured, expected):
    reader = harness.load_module(BENCH / "metrics"
                                 / "prefill_attn_roofline.serve.py")
    # bound by bytes: 2 prompts x 5 positions x (8 + 8 + 4 + 4) x 2 bytes
    # = 480 bytes a layer, 4 s at 120 bytes/s; 2 layers make 8 s
    peaks = Peaks(bf16_flops_per_s=1e12, hbm_bytes_per_s=120.0,
                  hbm_bytes=1, source="test")
    trace = SimpleNamespace(scope_time_s=lambda scope: measured)
    run = harness.Run({}, 0, 0, {}, 0,
                      {"batch": 2, "prompt_len": 5, "batches": 1})
    ctx = harness.Context(config=CFG, traffic={}, run=run, window_s=1.0,
                          compiles=0, peaks=peaks, trace=trace)
    got = reader.read(ctx)
    assert got == (None if expected is None else pytest.approx(expected))


def test_prefill_attention_work_counts_unmasked_pairs_only():
    flops, nbytes = work.prefill_attention_work(CFG, batch=1, seq_len=5)
    assert flops == 4 * 4 * 2 * 12
    assert nbytes == 2 * 5 * (8 + 8 + 4 + 4)


def test_serve_batch_flops_by_position():
    batch, prompt, new = 3, 4, 3
    by_hand = 0
    for pos in range(prompt + new - 1):      # last token never fed back
        ctx = min(pos + 1, CFG["sliding_window"])
        by_hand += (2 * work.layer_matmul_params(CFG) * 2
                    + 4 * 4 * 2 * ctx * 2)
    by_hand += 2 * 8 * 10 * new              # head: prefill's last + steps
    assert work.serve_batch_flops(CFG, batch, prompt, new) == batch * by_hand


def test_train_flops_are_three_forwards_per_token():
    s = 6
    fwd = work.forward_flops(CFG, s, work.attended_pairs(s, 3), s)
    assert work.train_flops_per_token(CFG, s) == pytest.approx(3 * fwd / s)


def test_least_time_names_its_bound():
    peaks = Peaks(bf16_flops_per_s=100.0, hbm_bytes_per_s=10.0,
                  hbm_bytes=1, source="test")
    assert work.least_time_s(1000, 50, peaks) == (10.0, "flops")
    assert work.least_time_s(100, 50, peaks) == (5.0, "bytes")


def test_peaks_refuse_an_unknown_kind():
    assert peaks_for("TPU v5 lite") is PEAKS["TPU v5 lite"]
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("devices, chips, refused", [
    ([("cpu", "cpu")], 1, "TPUs only"),
    ([("tpu", "TPU v9 imaginary")], 1, "no published peaks"),
    ([("tpu", "TPU v5 lite")], 4, "needs 4 chips"),
    ([("tpu", "TPU v5 lite")] * 4, 4, None),
])
def test_device_refusal(devices, chips, refused):
    devs = [SimpleNamespace(platform=p, device_kind=k) for p, k in devices]
    got = harness.device_refusal(devs, chips)
    assert (got is None) if refused is None else (refused in got)
