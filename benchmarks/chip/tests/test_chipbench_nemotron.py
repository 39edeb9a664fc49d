"""The Nemotron-H family (``families/nemotron_h.py``) and its cell: layer
and stage kinds of the committed configuration, the work counts at the
published widths by hand, the reference on dict stages, and a whole run
at CPU size."""
import copy
import time

import jax

from benchmarks.chip import harness, reference
from benchmarks.chip.families import nemotron_h as fam
from benchmarks.chip.peaks import peaks

CELL = "nemotron3nano-churn0"
SEED = 2 ** 31 + 17
# widths, vocabulary and sequence cut to CPU size: every kind, 2 B/C
# groups, 4 of 16 experts held
TINY = {"d_model": 64, "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
        "d_ff": 32, "shared_d_ff": 48, "vocab_size": 256, "ssm_state": 16,
        "ssm_heads": 8, "ssm_head_dim": 16, "ssm_groups": 2,
        "num_experts": 16, "experts_held": 4, "num_experts_per_tok": 3}
# Limits for this size, set as the committed ones are, from CPU readings
# (seeds 2**31 + 17 and 5: the sound program's largest grad_gap 0.021 and
# grad_median_gap 0.0022, the fp8 control's smallest 0.090 and 0.011).
TINY_LIMITS = {"grad_gap": 0.05, "grad_median_gap": 0.006}


def _tiny_spec():
    spec = harness.load_spec(CELL)
    spec.config = copy.deepcopy(spec.config)
    spec.traffic = copy.deepcopy(spec.traffic)
    spec.config["model"].update(TINY)
    spec.traffic["batch"].update({"microbatch": 2, "seq_len": 128})
    spec.limits = dict(TINY_LIMITS)
    return spec


def test_layer_and_stage_kinds_of_the_committed_config():
    m = harness.load_spec(CELL).config["model"]
    assert fam.layer_kinds(m) == ["mamba", "moe", "mamba", "moe", "mamba",
                                  "attention", "moe"]
    assert reference.stage_kinds(fam, m, 4) == [
        {"mamba": 1, "moe": 1}, {"mamba": 1, "moe": 1},
        {"mamba": 1, "attention": 1}, {"moe": 1}]


def test_counts_by_hand_at_the_published_widths():
    c = fam.counts(harness.load_spec(CELL).config["model"], 512)
    D = 2688
    # Mamba-2: in_proj to z 4096, xBC 4096 + 2*8*128, dt 64; out_proj;
    # conv 4 over 6144 channels; SSD at chunk 64: 8 groups' C.B, the
    # mixing over 64 heads of 64, state read-out and update
    mamba = (2 * D * 10304 + 2 * 4096 * D + 2 * 4 * 6144
             + 8 * 64 * 128 + 64 * 64 * 64 + 4 * 128 * 64 * 64)
    # MoE: router 128 wide, shared expert 3712, 6 * 8 / 128 held pairs of
    # relu^2 experts 1856 wide
    moe = 2 * D * 128 + 4 * D * 3712 + 6 * 8 / 128 * 4 * D * 1856
    # attention: 32 query and 2 KV heads of 128, causal half of 512
    attn = 2 * D * (4096 + 512) + 2 * 4096 * D + 2 * 512 * 4096
    assert c["layer_flops"] == {"mamba": mamba, "moe": moe,
                                "attention": attn}
    assert (mamba, moe, attn) == (79888384, 48082944.0, 50987008)
    assert c["head_flops"] == 2 * D * 16384
    assert c["layer_param_bytes"] == {
        "mamba": (D * 10304 + 4096 * D + 5 * 6144) * 2
                 + (D + 3 * 64 + 4096) * 4,
        "moe": (2 * D * 3712 + 8 * 2 * D * 1856) * 2 + (D * 128 + D) * 4,
        "attention": 2 * D * (4096 + 256) * 2 + D * 4}
    assert c["act_bytes"] == 2 * D


def test_reference_trains_two_steps_at_test_size():
    import numpy as np

    m = dict(harness.load_spec(CELL).config["model"], **TINY,
             param_dtype="float32")
    rng = np.random.default_rng(0)

    def mb():
        return {k: rng.integers(0, m["vocab_size"], (2, 64))
                for k in ("tokens", "labels")}

    steps = [{0: [mb(), mb()], 1: [mb()]}, {0: [mb(), mb()], 1: [mb()]}]
    completed = [[(0, 0), (0, 1), (1, 0)], [(0, 1), (1, 0)]]
    opt = harness.load_spec(CELL).traffic["optimizer"]
    run = reference.train(fam, m, 4, SEED, steps, completed, opt)
    rd = reference.readings(run, run)
    assert all(rd[k] == 0.0 for k in ("loss_gap", "grad_gap", "change_gap"))
    leaves = set(run["grad_norms"])
    assert {"stage0/mamba/mamba/in_proj", "stage1/moe/moe/w_up",
            "stage2/attention/attn/wq", "stage3/moe/moe/router",
            "stage3/moe/moe/shared/w_down", "head1/embed/lm_head"} <= leaves
    assert not any(n.startswith(("stage3/mamba", "stage0/attention"))
                   for n in leaves)
    control = reference.train(fam, m, 4, SEED, steps, completed, opt,
                              precision="fp8")
    assert reference.readings(control, run)["grad_gap"] > 0.0


def test_tiny_cell_is_correct_and_the_fp8_control_is_not():
    from benchmarks.chip import tracing

    spec = _tiny_spec()
    out = harness.run_cell(spec, SEED, 0.5, False, jax.devices(),
                           time.perf_counter(), peaks("TPU v5 lite"),
                           harness.CompileClock())
    assert out["correct"], out["checks"]
    assert out["checks"]["min_completed"]["value"] == 8
    trainer, shards = harness.build(spec, SEED)
    start = harness.give_weights(trainer, spec, SEED)
    checked = harness.checked_steps(trainer, shards, spec, start,
                                    tracing.Spans(False))
    low = reference.readings(
        harness.reference_run(spec, SEED, checked, precision="fp8"),
        harness.reference_run(spec, SEED, checked))
    assert any(low[k] > lim for k, lim in TINY_LIMITS.items()), low
