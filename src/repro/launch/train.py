"""End-to-end training driver.

Two modes:

* ``--mode spmd``  — single-program pjit training on the local device mesh
  (the path the production meshes would run; on CPU it uses the host
  devices).  Reduced configs train for real here.
* ``--mode gwtf``  — the paper's decentralized training: a FlowNetwork of
  data/relay nodes, GWTF flow routing, churn, and per-stage replicas via
  :class:`repro.core.executor.DecentralizedTrainer`.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch gwtf-llama-300m \
      --mode gwtf --stages 4 --iterations 50 --churn 0.1
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --mode spmd --reduced --steps 50
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def make_spmd(args, mesh):
    """Whole-model SPMD training state on ``mesh``.

    Returns ``(cfg, step_fn, params, opt_state, shard)``: params and AdamW
    state are created already placed by the sharding rules (each device
    holds its share, not a replica), and ``step_fn`` is the jitted step
    over those placements, donating the state it replaces.
    """
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, DataNodeShard
    from repro.launch.steps import make_train_step, train_shardings
    from repro.models.transformer import init_params
    from repro.optim.adamw import AdamW
    from repro.parallel.sharding import ShardingRules

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(num_layers=args.layers, d_model=args.d_model)
    rules = ShardingRules()
    opt = AdamW(lr=args.lr)
    key = jax.random.PRNGKey(args.seed)
    init = lambda k: init_params(cfg, k)
    params_abs = jax.eval_shape(init, key)
    opt_abs = jax.eval_shape(opt.init, params_abs)
    tok = jax.ShapeDtypeStruct((args.batch, args.seq_len), jnp.int32)
    (pspec, ospec, bspec), out_spec = train_shardings(
        cfg, params_abs, opt_abs, {"tokens": tok, "labels": tok}, rules,
        mesh)
    params = jax.jit(init, out_shardings=pspec)(key)
    opt_state = jax.jit(opt.init, out_shardings=ospec)(params)
    step_fn = jax.jit(make_train_step(cfg, opt, mesh=mesh, rules=rules),
                      in_shardings=(pspec, ospec, bspec),
                      out_shardings=out_spec, donate_argnums=(0, 1))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                    batch_size=args.batch, microbatch_size=args.batch,
                    seed=args.seed)
    return cfg, step_fn, params, opt_state, DataNodeShard(dc, 0, 1)


def run_spmd(args):
    from repro.checkpoint import store
    from repro.launch.mesh import make_host_mesh

    _, step_fn, params, opt_state, shard = make_spmd(args, make_host_mesh())
    for step in range(args.steps):
        t0 = time.time()
        params, opt_state, loss = step_fn(params, opt_state,
                                          shard.next_batch())
        if step % args.log_every == 0:
            print(f"step {step:4d} loss {float(loss):.4f} "
                  f"({time.time()-t0:.2f}s)")
    if args.checkpoint:
        store.save(args.checkpoint, params, step=args.steps)
        print("checkpoint ->", args.checkpoint)
    print(f"final loss {float(loss):.4f}")
    return float(loss)


def make_gwtf(args):
    """The paper's decentralized trainer and its data-node shards.

    Returns ``(cfg, trainer, shards)``; ``shards`` maps each data node's
    id to its :class:`~repro.data.pipeline.DataNodeShard`.
    """
    from repro.configs import get_config
    from repro.core.executor import DecentralizedTrainer
    from repro.core.flow.graph import geo_distributed_network
    from repro.data.pipeline import DataConfig, DataNodeShard

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(num_layers=max(args.stages, args.layers),
                          d_model=args.d_model)
    rng = np.random.default_rng(args.seed)
    caps = [args.capacity] * (args.stages * args.relays_per_stage)
    net = geo_distributed_network(
        num_stages=args.stages, relay_capacities=caps,
        num_data_nodes=args.data_nodes, data_capacity=args.microbatches,
        rng=rng)
    trainer = DecentralizedTrainer(cfg, net, churn=args.churn, lr=args.lr,
                                   seed=args.seed)
    shards = {d.id: DataNodeShard(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                   batch_size=args.microbatches * args.batch,
                   microbatch_size=args.batch, seed=args.seed + d.id),
        d.id, args.data_nodes) for d in net.data_nodes()}
    return cfg, trainer, shards


def run_gwtf(args):
    _, trainer, shards = make_gwtf(args)
    for it in range(args.iterations):
        batches = {dn: shards[dn].microbatches() for dn in shards}
        r = trainer.iteration(batches)
        print(f"iter {it:4d} loss {r.loss:.4f} "
              f"completed {r.completed}/{r.launched} dropped {r.dropped}")
    print(f"final loss {trainer.losses[-1]:.4f}")
    return trainer.losses[-1]


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gwtf-llama-300m")
    ap.add_argument("--mode", choices=("spmd", "gwtf"), default="gwtf")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--iterations", type=int, default=20)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--relays-per-stage", type=int, default=3)
    ap.add_argument("--capacity", type=int, default=4)
    ap.add_argument("--data-nodes", type=int, default=2)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--churn", type=float, default=0.0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--checkpoint", default=None)
    return ap


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = build_parser().parse_args()
    if args.mode == "spmd":
        run_spmd(args)
    else:
        run_gwtf(args)


if __name__ == "__main__":
    main()
