#!/usr/bin/env python3
"""Smoke check: the GWTF main path runs on a TPU at the paper's full width.

    python chip_smoke.py              # one chip: staged trainer + decode
    python chip_smoke.py --chips 4    # four chips: whole-model SPMD step only

One chip.  ``gwtf-llama-300m`` at its published config (16 layers,
d_model 1024, vocab 32000, bf16 params) trains through the staged
runtime the way ``repro.launch.train --mode gwtf`` builds it: 4 stages
x 3 relays, 1 data node, 8 microbatches of 4 x 512 tokens.  Four
iterations at churn 0, 3 at churn 0.1.  Checks: finite losses, the
loss falls over the churn-0 iterations, ``CentralizedTrainer`` gives
bit-identical churn-0 losses, one microbatch's loss matches a float32
reference within 2e-2, and the churn phase repairs at least one crash
(replay from stored residuals under real buffer donation).  Then
``ServeTrainer`` decodes 8 requests (prompt 128, 16 tokens) at the same
width, and every request must complete with 16 in-vocabulary tokens.

Four chips.  ``make_spmd``'s step runs 3 steps on the (1, 4) host mesh
and on a one-device mesh of the first chip; the losses must agree within
2e-2 and each chip must hold about a quarter of the params and AdamW
state.

It prints no times or memory figures: the chip benchmark
(``benchmarks/chip/run.py``) measures those.  The script exits non-zero,
and prints no result line, when any check fails or JAX finds no TPU.  A
passing run ends with one JSON line naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "gwtf-llama-300m"
SEED = 2                 # churn schedule chosen so the churn phase repairs
CHURN = 0.1              # at least one crash (host numpy: width-independent)
STAGES = 4
MICROBATCH, SEQ_LEN, N_MICROBATCHES = 4, 512, 8   # paper Sec. VI
CLEAN_ITERS, CHURN_ITERS = 4, 3
REF_RTOL = 2e-2
PROMPT_LEN, GEN_TOKENS = 128, 16
ARRIVALS = [[0.05, 0.1, 0.15, 0.2], [0.05, 0.1, 0.15, 0.2]]
SPMD_STEPS = 3

TRAIN_FLAGS = ["--arch", ARCH, "--mode", "gwtf", "--stages", str(STAGES),
               "--relays-per-stage", "3", "--capacity", "4",
               "--data-nodes", "1", "--microbatches", str(N_MICROBATCHES),
               "--batch", str(MICROBATCH), "--seq-len", str(SEQ_LEN),
               "--lr", "1e-3", "--seed", str(SEED)]
SPMD_FLAGS = ["--arch", ARCH, "--mode", "spmd", "--batch", str(MICROBATCH),
              "--seq-len", str(SEQ_LEN), "--lr", "1e-3", "--seed", str(SEED)]


class Checks:
    """Named pass/fail results; the run passes only if every one did."""

    def __init__(self):
        self.failed: list = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"check {name}: {'ok' if ok else 'FAIL'} {detail}".rstrip(),
              flush=True)
        if not ok:
            self.failed.append(name)


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def reference_check(check: Checks, trainer, cfg, mb, seed: int) -> None:
    """One microbatch through the timed path's compiled stage programs
    against the same initial params upcast to float32 and run at the
    highest matmul precision."""
    import jax
    import jax.numpy as jnp

    from repro.core.runtime import cache
    from repro.core.runtime.stages import embed_fn, loss_fn, stage_forward

    stage_p, head_p = cache.initial_params(cfg, STAGES, seed)
    toks = jnp.asarray(mb["tokens"])
    labels = jnp.asarray(mb["labels"])
    st = trainer.stages
    x = st.embed(head_p, toks)
    for s in range(STAGES):
        x, _ = st.forward_fused(s, stage_p[s], x)
    losses, _, _ = st.head_loss(head_p, x[None], labels[None])
    timed = float(losses[0])

    cfg32 = dataclasses.replace(cfg, param_dtype="float32")
    up = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)

    @jax.jit
    def ref_loss(stage_ps, head, toks, labels):
        h = embed_fn(head, toks)
        for p in stage_ps:
            h = stage_forward(p, h, cfg32)
        return loss_fn(head, h, labels, cfg32)

    with jax.default_matmul_precision("highest"):
        ref = float(ref_loss(up(stage_p), up(head_p), toks, labels))
    rel = abs(timed - ref) / abs(ref)
    check("float32-reference", math.isfinite(timed) and rel <= REF_RTOL,
          f"timed={timed!r} reference={ref!r} rel={rel:.3e} "
          f"limit={REF_RTOL}")


def train_phase(check: Checks, flags) -> None:
    from repro.core.executor import CentralizedTrainer
    from repro.core.sim.faults import BernoulliChurn
    from repro.launch.train import build_parser, make_gwtf

    args = build_parser().parse_args(flags)
    cfg, trainer, shards = make_gwtf(args)
    print(f"train config: {cfg.name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} vocab={cfg.vocab_size} "
          f"param_dtype={cfg.param_dtype} stages={args.stages} "
          f"relays/stage={args.relays_per_stage} microbatches="
          f"{args.microbatches}x{args.batch}x{args.seq_len} "
          f"donate={trainer.stages.donate}", flush=True)
    (dn,) = shards

    clean_batches = []
    for _ in range(CLEAN_ITERS):
        batches = {dn: shards[dn].microbatches()}
        clean_batches.append(batches[dn])
        trainer.iteration(batches)
    clean = list(trainer.losses)

    reference_check(check, trainer, cfg, clean_batches[0][0], args.seed)

    cen = CentralizedTrainer(cfg, args.stages, lr=args.lr, seed=args.seed)
    for mbs in clean_batches:
        cen.iteration(mbs)
    check("churn0-decentralized==centralized", cen.losses == clean,
          f"decentralized={clean!r} centralized={cen.losses!r}")
    del cen

    trainer.churn_model = BernoulliChurn(CHURN)
    repaired = 0
    for _ in range(CHURN_ITERS):
        r = trainer.iteration({dn: shards[dn].microbatches()})
        repaired += r.rerouted + r.fwd_recomputes + r.bwd_replays
    losses = trainer.losses
    check("losses-finite", all(math.isfinite(x) for x in losses),
          f"losses={losses!r}")
    check("loss-falls-at-churn-0", clean[-1] < clean[0],
          f"first={clean[0]!r} after_churn0={clean[-1]!r}")
    check("churn-phase-repairs-a-crash", repaired > 0,
          f"rerouted+fwd_recomputes+bwd_replays={repaired}")


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def decode_phase(check: Checks, cfg) -> None:
    import numpy as np

    from repro.core.flow.graph import geo_distributed_network
    from repro.core.runtime.serving import ServeTrainer
    from repro.core.sim.metrics import ModelProfile
    from repro.core.sim.policies import GWTFPolicy

    rng = np.random.default_rng(SEED)
    net = geo_distributed_network(
        num_stages=STAGES, relay_capacities=[4] * (2 * STAGES),
        num_data_nodes=1, data_capacity=N_MICROBATCHES, rng=rng)
    n_req = sum(len(a) for a in ARRIVALS)
    serve = ServeTrainer(
        cfg, net, policy=GWTFPolicy(net, rng=rng), arrival_program=ARRIVALS,
        profile=ModelProfile.from_config(cfg, num_stages=STAGES,
                                         microbatch=MICROBATCH,
                                         seq_len=SEQ_LEN),
        prompt_len=PROMPT_LEN, gen_tokens=GEN_TOKENS, serve_batch=4,
        tokens_per_mb=MICROBATCH * SEQ_LEN, rng=rng, seed=SEED,
        max_requests=n_req)
    print(f"decode config: {cfg.name} d_model={cfg.d_model} "
          f"prompt_len={PROMPT_LEN} gen_tokens={GEN_TOKENS} "
          f"requests={n_req} iterations={len(ARRIVALS)}", flush=True)
    admitted = sum(serve.iteration().admitted for _ in ARRIVALS)
    recs = serve.engine.requests
    done = [rid for rid, rec in recs.items() if rec.completion is not None]
    streams = {rid: serve.token_stream(rid) for rid in recs}
    good = [rid for rid in done if len(streams[rid]) == GEN_TOKENS
            and all(0 <= t < cfg.vocab_size for t in streams[rid])]
    check("decode-requests-complete",
          admitted == n_req and len(good) == len(recs) == n_req,
          f"admitted={admitted} completed={len(done)} "
          f"with_{GEN_TOKENS}_in_vocab_tokens={len(good)} of {n_req}")


# ---------------------------------------------------------------------------
# SPMD across chips
# ---------------------------------------------------------------------------

def _bytes_by_device(tree):
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def _spmd_run(flags, mesh, label: str):
    import jax

    from repro.launch.train import build_parser, make_spmd

    args = build_parser().parse_args(flags)
    cfg, step_fn, params, opt_state, shard = make_spmd(args, mesh)
    total = sum(x.nbytes for x in jax.tree.leaves((params, opt_state)))
    held = _bytes_by_device((params, opt_state))
    print(f"spmd {label}: {cfg.name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} vocab={cfg.vocab_size} "
          f"param_dtype={cfg.param_dtype} mesh={dict(mesh.shape)} "
          f"batch={args.batch}x{args.seq_len}", flush=True)
    losses = []
    for _ in range(SPMD_STEPS):
        params, opt_state, loss = step_fn(params, opt_state,
                                          shard.next_batch())
        losses.append(float(loss))
    return losses, held, total


def spmd_phase(check: Checks, flags, devices) -> None:
    from repro.launch.mesh import make_host_mesh

    n = len(devices)
    multi, held, total = _spmd_run(flags, make_host_mesh(devices),
                                   f"{n}-device")
    share = max(held.values()) / total
    check("spmd-state-sharded", len(held) == n and share <= 1.5 / n,
          f"largest_device_share={share:.4f} devices={len(held)}")
    single, _, _ = _spmd_run(flags, make_host_mesh(devices[:1]),
                             "1-device")
    rel = max(abs(a - b) / abs(b) for a, b in zip(multi, single))
    check(f"spmd-{n}-device-vs-1-device",
          all(math.isfinite(x) for x in multi + single) and rel <= REF_RTOL,
          f"{n}-device={multi!r} 1-device={single!r} max_rel={rel:.3e} "
          f"limit={REF_RTOL}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the SPMD path across four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform is "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    print(f"device: platform=tpu kind={kind} count={len(devices)}",
          flush=True)

    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    check = Checks()
    if args.chips == 4:
        spmd_phase(check, SPMD_FLAGS, devices[:4])
    else:
        train_phase(check, TRAIN_FLAGS)
        from repro.configs import get_config
        decode_phase(check, get_config(ARCH))
    if check.failed:
        print(f"chip_smoke: FAILED {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
