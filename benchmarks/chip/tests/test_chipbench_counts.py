"""FLOP and byte counts against hand counts at small shapes, the peaks
table, and the roofline arithmetic."""
from types import SimpleNamespace

import pytest

from benchmarks.chip import peaks, work
from benchmarks.chip.families import gpt, mamba2

GPT = {"d_model": 8, "d_ff": 16, "num_heads": 2, "num_kv_heads": 2,
       "head_dim": 4, "vocab_size": 10, "param_dtype": "bfloat16"}
MAMBA = {"d_model": 4, "ssm_expand": 2, "ssm_heads": 2, "ssm_state": 3,
         "ssm_conv": 4, "vocab_size": 10, "param_dtype": "bfloat16"}


def test_gpt_counts_by_hand():
    c = gpt.counts(GPT, seq_len=6)
    # q,k,v: 3 x (8x8) and wo 8x8, at 2 FLOPs per multiply-add
    proj = 2 * 8 * 8 * 3 + 2 * 8 * 8
    # causal QK^T and PV: each 6*6/2 score entries x 4 dims x 2 heads x 2
    # FLOPs per sequence, i.e. 2 * 6 * 8 per token for the pair
    attn = 2 * (6 * 6 / 2 * 4 * 2 * 2) / 6
    mlp = 2 * 8 * 16 * 2
    assert c["layer_flops"] == proj + attn + mlp == 1120
    assert c["head_flops"] == 2 * 8 * 10
    # 4 square projections + two 8x16 MLP matrices in bf16, 4 norm
    # vectors of 8 in float32
    assert c["layer_param_bytes"] == (4 * 64 + 2 * 128) * 2 + 4 * 8 * 4
    assert c["act_bytes"] == 8 * 2


def test_mamba2_counts_by_hand():
    c = mamba2.counts(MAMBA, seq_len=64)
    di, H, P, N, K, Q = 8, 2, 4, 3, 4, mamba2.SSD_CHUNK
    in_proj = 2 * 4 * (2 * 8 + 2 * 3 + 2)       # z, x, B, C, dt
    out_proj = 2 * 8 * 4
    conv = 2 * 4 * (8 + 2 * 3)
    # causal half of C.B (Q x Q x N) and of the mixing (Q x Q x P per
    # head), per token; then C.h and the state update, 2*N*P per head
    ssd = Q * N + Q * P * H + 2 * (2 * N * P * H)
    assert c["layer_flops"] == in_proj + out_proj + conv + ssd
    assert c["head_flops"] == 2 * 4 * 10
    stored = 4 * 24 + 8 * 4 + (K + 1) * 14
    assert c["layer_param_bytes"] == stored * 2 + (4 + 3 * H + di) * 4


def test_gpt300m_needs_about_1_57_gflop_per_trained_token():
    m = dict(GPT, d_model=1024, d_ff=4096, num_heads=16, num_kv_heads=16,
             head_dim=64, vocab_size=50257)
    rec = SimpleNamespace(counts=gpt.counts(m, 512), stage_layers=[4] * 4)
    assert work.train_flops_per_token(rec) == pytest.approx(1.567e9,
                                                            rel=1e-3)


def _rec(seconds):
    c = gpt.counts(GPT, 6)
    return SimpleNamespace(
        counts=c, stage_layers=[2, 2], tokens_per_mb=12, completed=8,
        iterations=1, bwd_replays=2, chips=1,
        peaks=peaks.peaks("TPU v5 lite"),
        trace={"program_s": {"jit_fwd_res_impl": seconds,
                             "jit_bwd_res_impl": seconds}})


def test_roofline_is_least_time_over_measured_time():
    rec = _rec(1.0)
    flops, nbytes = work.stage_pass(rec, "fwd")
    assert flops == 8 * 4 * 1120 * 12
    assert nbytes == 4 * rec.counts["layer_param_bytes"] + 16 * 2 * 12 * 16
    least = max(flops / 197e12, nbytes / 819e9)
    share = work.roofline(rec, "fwd_res_impl", "fwd")
    assert share == pytest.approx(100 * least)
    # time equal to the least time reads exactly 100 %
    assert work.roofline(_rec(least), "fwd_res_impl", "fwd") == \
        pytest.approx(100.0)


def test_backward_counts_replays_and_gradients():
    rec = _rec(1.0)
    f_fwd, _ = work.stage_pass(rec, "fwd")
    f_bwd, b_bwd = work.stage_pass(rec, "bwd")
    assert f_bwd == pytest.approx(2 * f_fwd * (16 + 2) / 16)
    assert b_bwd == 2 * 4 * rec.counts["layer_param_bytes"] + 18 * 2 * 12 * 16


def test_no_program_in_the_trace_reads_nothing():
    rec = _rec(1.0)
    rec.trace = {}
    assert work.roofline(rec, "fwd_res_impl", "fwd") is None


def test_unknown_device_is_an_error():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("cpu")
