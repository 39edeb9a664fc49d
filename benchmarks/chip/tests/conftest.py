"""Fixtures for the chip benchmark's CPU tests: the repo root and ``src``
on the path, and cells shrunk to a size the CPU runs in seconds."""
import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODELS = {
    "gpt": {"num_layers": 4, "d_model": 64, "num_heads": 2,
            "num_kv_heads": 2, "head_dim": 32, "d_ff": 128,
            "vocab_size": 256},
    "mamba2": {"num_layers": 4, "d_model": 64, "ssm_heads": 4,
               "ssm_state": 16, "vocab_size": 256},
}
TINY_BATCH = {"gpt": {"microbatch": 2, "seq_len": 32},
              "mamba2": {"microbatch": 2, "seq_len": 128}}
# The committed limits are set at each cell's own widths on the chip.  At
# CPU size the program's bf16 rounding reads differently, so the tests
# hold the same numbers to limits set the same way from CPU readings at
# this size (three seeds: the sound program's largest reading, the fp8
# control's smallest, the limit between them).
TINY_LIMITS = {
    "gpt": {"loss1_gap": 4e-05, "loss_gap": 0.02, "grad_gap": 0.01,
            "grad_median_gap": 0.002, "change_gap": 0.005},
    "mamba2": {"loss_gap": 0.004, "grad_gap": 0.04,
               "grad_median_gap": 0.003, "change_gap": 0.03},
}


def tiny_spec(workload: str):
    """The committed cell with its widths, vocabulary and sequence cut
    to CPU size, and limits for that size; topology and churn as
    committed."""
    from benchmarks.chip.harness import load_spec

    spec = load_spec(workload)
    spec.config = copy.deepcopy(spec.config)
    spec.traffic = copy.deepcopy(spec.traffic)
    fam = spec.config["family"]
    spec.config["model"].update(TINY_MODELS[fam])
    spec.traffic["batch"].update(TINY_BATCH[fam])
    spec.limits = dict(TINY_LIMITS[fam])
    return spec
