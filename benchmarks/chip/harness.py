"""One run of one cell: set-up, the measured window, the reference check
and the metrics.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file, ``traffic/<traffic>.json``, ``limits/<cell>.json``,
the model family ``families/<family>.py`` that the configuration names,
and one reader ``metrics/<metric>.py`` per metric.  Adding a cell adds
files and entries; nothing here names a cell.

The program under test is the staged GWTF trainer, built the way
``repro.launch.train.make_gwtf`` builds it.  The benchmark gives it the
weights (made from the seed in one jitted call), the data nodes'
shards and the churn trace, and takes from it only its iteration
results, its optimizer state after the first step, its parameters after
the checked steps, and the device trace.

A model family gives its layers in one of two forms, set out in
``reference.py``: homogeneous, each stage one tree of layer-stacked
leaves, or kinded (``layer_kinds``), each stage a dict ``{kind: that
kind's layers in the stage, stacked in layer order}`` with absent kinds
left out.  The program must hold ``trainer.stage_params[s]`` in that
same layout, shapes and dtypes; ``give_weights`` refuses it otherwise.
"""
from __future__ import annotations

import gc
import importlib
import json
import math
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


# ---------------------------------------------------------------------------
# Finding a cell's files
# ---------------------------------------------------------------------------

def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str, root: Path = ROOT) -> SimpleNamespace:
    bench = _load(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    return SimpleNamespace(
        workload=w, config=_load(root / conf["file"]),
        traffic=_load(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_load(HERE / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if listed(m)],
        per_layer=[m for m in bench["per_layer"] if listed(m)])


def family_of(spec):
    return importlib.import_module(
        f"benchmarks.chip.families.{spec.config['family']}")


# ---------------------------------------------------------------------------
# Compile events
# ---------------------------------------------------------------------------

class CompileClock:
    """Compile events from ``jax.monitoring``: seconds of tracing,
    lowering and compiling in set-up, and the number of programs lowered
    in the window (each one a compile or a cache fetch)."""

    LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.phase = "setup"
        self.setup_s = 0.0
        self.window_lowered = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.phase == "setup" and event.startswith("/jax/core/compile/"):
            self.setup_s += duration
        elif self.phase == "window" and event == self.LOWERED:
            self.window_lowered += 1


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

def build(spec, seed: int):
    """The network, the trainer and the data nodes' shards, built as
    ``make_gwtf`` builds them, with the traffic file's topology, batch,
    optimizer and churn.  The network (links, locations, compute costs)
    is the traffic file's fixed cluster, drawn from its
    ``network_seed``; the run's seed draws the data, the weights, the
    planner's stream and the churn trace."""
    from repro.core.executor import DecentralizedTrainer
    from repro.core.flow.graph import geo_distributed_network
    from repro.core.sim.faults import TraceChurn
    from repro.data.pipeline import DataConfig, DataNodeShard
    from repro.models.config import ModelConfig

    from benchmarks.chip.churn import crash_and_return

    cfg = ModelConfig(**spec.config["model"])
    topo, batch, tr = (spec.traffic["topology"], spec.traffic["batch"],
                       spec.traffic)
    S = topo["stages"]
    caps = topo.get("capacities") or (
        [topo["capacity"]] * (S * topo["relays_per_stage"]))
    net = geo_distributed_network(
        num_stages=S, relay_capacities=caps,
        num_data_nodes=topo["data_nodes"],
        data_capacity=batch["microbatches_per_data_node"],
        rng=np.random.default_rng(topo["network_seed"]))
    churn_model = None
    if tr.get("churn"):
        c = tr["churn"]
        relays = {s: [n.id for n in net.stage_nodes(s)] for s in range(S)}
        events = crash_and_return(
            relays, rate=c["rate"], block=c["block"], horizon=c["horizon"],
            check_crashes=c.get("check_crashes", ()),
            rng=np.random.default_rng([seed, 1]))
        churn_model = TraceChurn(events, known_ids=net.nodes.keys())
    trainer = DecentralizedTrainer(cfg, net, churn=0.0,
                                   lr=tr["optimizer"]["lr"], seed=seed,
                                   churn_model=churn_model)
    per, n_mb = batch["microbatch"], batch["microbatches_per_data_node"]
    shards = {d.id: DataNodeShard(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=batch["seq_len"],
                   batch_size=n_mb * per, microbatch_size=per,
                   seed=seed + d.id), d.id, topo["data_nodes"])
        for d in net.data_nodes()}
    return trainer, shards


def _same_layout(a, b) -> bool:
    import jax

    la, lb = jax.tree_util.tree_flatten(a), jax.tree_util.tree_flatten(b)
    return la[1] == lb[1] and all(
        x.shape == y.shape and x.dtype == y.dtype
        for x, y in zip(la[0], lb[0]))


def give_weights(trainer, spec, seed: int):
    """Replace the trainer's initial weights with the benchmark's own,
    made from the seed; returns them (the checked steps' start)."""
    from benchmarks.chip.reference import init_weights

    S = trainer.net.num_stages
    stages, head = init_weights(family_of(spec), spec.config["model"], S,
                                seed)
    dns = list(trainer.head_params)
    if not (_same_layout(list(stages), trainer.stage_params)
            and _same_layout(head, trainer.head_params[dns[0]])):
        raise RuntimeError("the program's parameter layout differs from "
                           "the benchmark's weights for this family")
    trainer.stage_params = list(stages)
    trainer.head_params = {dn: head for dn in dns}
    start = {f"stage{s}": p for s, p in enumerate(stages)}
    start.update({f"head{dn}": head for dn in dns})
    return start


def _trees(trainer, which: str):
    if which == "params":
        out = {f"stage{s}": p for s, p in enumerate(trainer.stage_params)}
        out.update({f"head{dn}": p for dn, p in trainer.head_params.items()})
    else:
        out = {f"stage{s}": o.m for s, o in enumerate(trainer.stage_opt)}
        out.update({f"head{dn}": o.m for dn, o in trainer.head_opt.items()})
    return out


def checked_steps(trainer, shards, spec, start, spans) -> dict:
    """The first steps, through the window's own call and feed.

    Records what the reference needs (the microbatches fed, which of
    them completed) and what it is compared with (each step's loss, the
    first gradient as AdamW received it, from its first moment, and the
    parameters' change over the steps)."""
    from benchmarks.chip.reference import named_norms

    b1 = spec.traffic["optimizer"]["b1"]
    fed, completed, losses, results, grad_norms = [], [], [], [], None
    for t in range(spec.traffic["check_steps"]):
        with spans("data"):
            batches = {dn: sh.microbatches() for dn, sh in shards.items()}
        with spans("iteration"):
            r = trainer.iteration(batches)
        where = {id(mb): (dn, k) for dn, mbs in batches.items()
                 for k, mb in enumerate(mbs)}
        completed.append([where[id(j.mb)]
                          for j in trainer.last_resolution.completed])
        fed.append(batches)
        losses.append(float(r.loss))
        results.append(r)
        if t == 0:
            grad_norms = {k: v / (1.0 - b1) for k, v in
                          named_norms(_trees(trainer, "moments")).items()}
    change = named_norms(_trees(trainer, "params"), minus=start)
    return {"fed": fed, "completed": completed, "results": results,
            "program": {"losses": losses, "grad_norms": grad_norms,
                        "change_norms": change}}


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

def reference_run(spec, seed: int, checked: dict, **kw) -> dict:
    """The reference over the checked steps' microbatches (``kw``:
    ``precision``, ``half_batch``; see ``reference.train``)."""
    from benchmarks.chip import reference

    return reference.train(
        family_of(spec), spec.config["model"],
        spec.traffic["topology"]["stages"], seed, checked["fed"],
        checked["completed"], spec.traffic["optimizer"], **kw)


def structural(spec, checked: dict) -> dict:
    """What the checked steps must cover: completed microbatches, and in
    a churn cell a forward and a backward repair."""
    res = checked["results"]
    out = {"min_completed": {
        "value": min(len(c) for c in checked["completed"]),
        "limit": spec.traffic["min_completed_per_check_step"],
        "rule": ">="}}
    if spec.traffic.get("churn"):
        out["fwd_repairs"] = {"value": sum(r.fwd_recomputes for r in res),
                              "limit": 1, "rule": ">="}
        out["bwd_repairs"] = {"value": sum(r.bwd_replays for r in res),
                              "limit": 1, "rule": ">="}
    return out


def judge(spec, rd: dict, struct: dict) -> dict:
    """The compared numbers: every reading the cell's limits file names,
    then the structural checks."""
    checks = {k: {"value": rd[k], "limit": lim, "rule": "<="}
              for k, lim in spec.limits.items()}
    checks.update(struct)
    return checks


def holds(c: dict) -> bool:
    v, lim = c["value"], c["limit"]
    if not (isinstance(v, (int, float)) and math.isfinite(v)):
        return False
    return v <= lim if c["rule"] == "<=" else v >= lim


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def record(spec, counters, **measured) -> SimpleNamespace:
    """What the metric readers read: the window's counters and
    measurements, with the cell's shapes and work counts (``work.py``):
    ``stage_layers``, and for a kinded family ``stage_kinds``."""
    from benchmarks.chip.reference import stage_bounds, stage_kinds

    family, model = family_of(spec), spec.config["model"]
    batch, S = spec.traffic["batch"], spec.traffic["topology"]["stages"]
    return SimpleNamespace(
        **vars(counters), **measured,
        tokens_per_mb=batch["microbatch"] * batch["seq_len"],
        seq_len=batch["seq_len"], model=model,
        counts=family.counts(model, batch["seq_len"]),
        stage_layers=[hi - lo for lo, hi in stage_bounds(
            model["num_layers"], S)],
        stage_kinds=stage_kinds(family, model, S), trace={})


def run_cell(spec, seed: int, seconds: float, trace: bool, devices,
             t_process: float, peaks: dict, clock: CompileClock,
             trace_dir: str = None) -> dict:
    """Set-up, window, reference check and metrics; returns the result
    object (the last line of standard output)."""
    import jax

    from benchmarks.chip import tracing
    from benchmarks.chip.reference import readings

    spans = tracing.Spans(trace)
    trainer, shards = build(spec, seed)
    start = give_weights(trainer, spec, seed)
    checked = checked_steps(trainer, shards, spec, start, spans)
    del start
    jax.block_until_ready((trainer.stage_params, trainer.head_params))

    # ---- window ---------------------------------------------------------
    clock.phase = "window"
    if trace:
        jax.profiler.start_trace(trace_dir)
    n = SimpleNamespace(iterations=0, completed=0, launched=0, dropped=0,
                        fwd_recomputes=0, bwd_replays=0, data_s=0.0)
    t0 = time.perf_counter()
    with spans("window"):
        while True:
            d0 = time.perf_counter()
            with spans("data"):
                batches = {dn: sh.microbatches() for dn, sh in shards.items()}
            n.data_s += time.perf_counter() - d0
            with spans("iteration"):
                r = trainer.iteration(batches)
            n.iterations += 1
            n.completed += r.completed
            n.launched += r.launched
            n.dropped += r.dropped
            n.fwd_recomputes += r.fwd_recomputes
            n.bwd_replays += r.bwd_replays
            if time.perf_counter() - t0 >= seconds:
                break
        with spans("sync"):
            jax.block_until_ready((trainer.stage_params, trainer.head_params))
    t1 = time.perf_counter()
    clock.phase = "after"
    if trace:
        jax.profiler.stop_trace()
    setup_s = t0 - t_process
    peak = int((devices[0].memory_stats() or {}).get("peak_bytes_in_use", 0))

    # ---- free the program, then the reference ---------------------------
    del trainer, shards, r, batches
    from repro.core.runtime import cache
    cache.clear()
    gc.collect()
    rd = readings(checked["program"], reference_run(spec, seed, checked))
    checks = judge(spec, rd, structural(spec, checked))
    correct = all(holds(c) for c in checks.values())

    # ---- metrics --------------------------------------------------------
    rec = record(spec, n, window_s=t1 - t0, setup_s=setup_s,
                 setup_compile_s=clock.setup_s,
                 window_lowered=clock.window_lowered, peak_bytes=peak,
                 chips=len(devices), peaks=peaks)
    breakdown = None
    if trace:
        rec.trace = tracing.reduce(tracing.extract(trace_dir))
        if rec.trace:
            breakdown = {"device_ops": rec.trace["device_ops"],
                         "idle_gaps": rec.trace["idle_gaps"]}
    metrics = {}
    for m in (spec.per_layer if trace else spec.end_to_end):
        reader = importlib.import_module(f"benchmarks.chip.metrics.{m['name']}")
        v = reader.read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace and rec.trace:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
    print("readings: " + json.dumps(rd), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} {c['rule']} {c['limit']!r} "
              f"{'ok' if holds(c) else 'FAIL'}", file=sys.stderr, flush=True)
    out = {"correct": correct, "attempted": n.launched, "failed": n.dropped,
           "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": _plain(c["value"]), "limit": c["limit"],
                         "rule": c["rule"]} for k, c in checks.items()}
    return out


def _plain(v):
    """A number as JSON can hold it (a non-finite reading as a string)."""
    return v if math.isfinite(v) else str(v)
