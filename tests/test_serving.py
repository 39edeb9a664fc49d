"""Serving-plane test tier.

Promoted from the old ``test_serve_smoke.py``: the standalone
``launch/serve.py`` driver and ``examples/serve_decode.py`` smoke
coverage rides along unchanged, joined by the churn-tolerant serving
plane proper — seeded RNG-key discipline, request conservation,
continuous-batching bit-equivalence against the standalone decode
path, crash-mid-decode requeue recovering the exact token stream, and
KV-residency pricing monotonicity on the flow graph.

Fast checks run in tier 1; the crash-recovery differential (three full
real-compute serving runs) lives behind ``-m scenarios`` next to the
corpus sweep.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.runtime.serving import serving_inputs, serving_keys
from repro.core.scenarios import generate
from repro.core.scenarios.harness import (check_serving_consistency,
                                          check_serving_invariants)
from repro.core.scenarios.spec import ScenarioSpec
from repro.core.sim.metrics import summarize_serving

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _serving_spec(**overrides) -> ScenarioSpec:
    """Tiny 3-stage geo serving scenario shared by the tests below."""
    kw = dict(
        name="t-serve", seed=26, num_stages=3,
        relays_per_stage=3, num_data_nodes=1, iterations=2,
        model_layers=2, model_d=32, model_vocab=128, seq_len=16,
        microbatch_size=1,
        arrivals=[{"kind": "spike", "at_iteration": 0,
                   "requests": 3, "when": 0.2}],
        prompt_len=8, gen_tokens=16, serve_batch=4)
    kw.update(overrides)
    spec = ScenarioSpec(**kw)
    spec.validate()
    return spec


# ---------------------------------------------------------------------------
# Satellite 1: seeded key discipline (the launch/serve.py RNG fix)
# ---------------------------------------------------------------------------

def test_serving_keys_distinct_and_reproducible():
    """``serving_keys`` must fan one seed into four *distinct* streams
    (params / prompt / aux / sampling — the old driver reused one key
    for all of them) and be a pure function of the seed."""
    def raw(keys):
        return [tuple(np.asarray(k).ravel().tolist()) for k in keys]

    keys = serving_keys(7)
    assert len(keys) == 4
    first = raw(keys)
    assert len(set(first)) == 4, "key streams must not collide"
    assert first == raw(serving_keys(7))
    assert first != raw(serving_keys(8))


def test_serving_inputs_seeded_determinism():
    """Params/prompt/sampling material is bit-reproducible per seed and
    the prompt stream is decoupled from the param stream."""
    from repro.configs import get_config
    cfg = get_config("tinyllama-1.1b").reduced(num_layers=2, d_model=64)
    a = serving_inputs(cfg, seed=3, batch=2, prompt_len=8)
    b = serving_inputs(cfg, seed=3, batch=2, prompt_len=8)
    assert all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree_util.tree_leaves(a[:2]),
                   jax.tree_util.tree_leaves(b[:2])))
    c = serving_inputs(cfg, seed=4, batch=2, prompt_len=8)
    assert not bool(jnp.array_equal(a[1], c[1]))


@pytest.fixture()
def no_compile_cache(monkeypatch):
    """The CLI turns on the persistent compile cache for its process; a
    test process keeps its compiles to itself."""
    import repro.launch.serve as serve

    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)


def test_serve_driver_seeded_determinism(monkeypatch, capsys,
                                        no_compile_cache):
    """Two driver runs with the same ``--seed`` emit identical sampled
    tokens; a different seed diverges (the pre-fix driver fed the same
    key to init and to every sampling step)."""
    import repro.launch.serve as serve

    def run(seed):
        monkeypatch.setattr(sys, "argv", [
            "serve", "--arch", "tinyllama-1.1b", "--reduced", "--layers",
            "2", "--d-model", "64", "--batch", "1", "--prompt-len", "8",
            "--gen", "3", "--temperature", "1.0", "--seed", str(seed)])
        serve.main()
        out = capsys.readouterr().out
        return [ln for ln in out.splitlines() if "sample:" in ln]

    first = run(11)
    assert first, "driver printed no sampled tokens"
    assert first == run(11)
    assert first != run(12)


# ---------------------------------------------------------------------------
# Absorbed smoke coverage (formerly tests/test_serve_smoke.py)
# ---------------------------------------------------------------------------

def test_serve_driver_tiny_decode(monkeypatch, capsys, no_compile_cache):
    """Run the real `repro.launch.serve` CLI end to end on a reduced
    config: prefill + 2 greedy decode steps."""
    import repro.launch.serve as serve

    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "tinyllama-1.1b", "--reduced", "--layers", "2",
        "--d-model", "64", "--batch", "1", "--prompt-len", "8",
        "--gen", "2"])
    serve.main()
    out = capsys.readouterr().out
    assert "prefill: bs=1 len=8" in out
    assert "decoded 2 steps" in out


def test_serve_driver_long_mode(monkeypatch, capsys, no_compile_cache):
    """The sliding-window ring-buffer path (--long) decodes past the
    window without growing the cache."""
    import repro.launch.serve as serve

    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "tinyllama-1.1b", "--reduced", "--layers", "2",
        "--d-model", "64", "--batch", "1", "--prompt-len", "8",
        "--gen", "2", "--long", "--window", "16"])
    serve.main()
    assert "ring-buffer" in capsys.readouterr().out


def test_serve_example_imports_and_decode_path_runs():
    """`examples/serve_decode.py` parses/compiles, and the exact code
    path it demonstrates (sliding-window prefill + jitted decode_step)
    works on a smaller-than-example shape."""
    from repro.configs import get_config
    from repro.models.transformer import (decode_step, init_cache,
                                          init_params, prefill)

    path = os.path.join(_REPO, "examples", "serve_decode.py")
    with open(path) as fh:
        compile(fh.read(), path, "exec")     # syntax/shape of the stub
    cfg = dataclasses.replace(
        get_config("tinyllama-1.1b").reduced(num_layers=2, d_model=64),
        sliding_window=16)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    window = cfg.sliding_window
    cache = init_cache(cfg, 1, window, dtype=jnp.float32)
    prompt = jax.random.randint(key, (1, 8), 0, cfg.vocab_size)
    logits, cache = prefill(params, cfg, tokens=prompt, cache=cache)
    assert logits.shape[0] == 1
    step = jax.jit(lambda p, tok, c, i: decode_step(
        p, cfg, tokens=tok, cache=c, index=i, window=window))
    tok = jnp.argmax(logits, -1)[:, None]
    for i in range(2):
        logits, cache = step(params, tok, cache, jnp.int32(8 + i))
        tok = jnp.argmax(logits, -1)[:, None]
    assert tok.shape == (1, 1)
    assert int(tok[0, 0]) < cfg.vocab_size


# ---------------------------------------------------------------------------
# Satellite 2: serving invariants and differentials
# ---------------------------------------------------------------------------

def test_request_conservation_invariant():
    """admitted == completed + dropped + in_flight at every iteration
    boundary, plus the rest of the pure-sim invariant battery (seeded
    rerun identity, arrival accounting, TTFT ordering)."""
    spec = _serving_spec(
        gen_tokens=8,
        arrivals=[{"kind": "poisson", "rate": 2.0},
                  {"kind": "spike", "at_iteration": 1,
                   "requests": 4, "when": 0.3}],
        churn=[{"kind": "trace", "events": [(1, "crash", 5, 0.45)]}],
        iterations=3)
    out = check_serving_invariants(spec)
    assert out["admitted"] >= 4
    assert out["admitted"] == (out["completed"] + out["dropped"]
                               + out["summary"]["in_flight"])


def test_kv_residency_pricing_monotonicity():
    """Eq. 1 destination surcharge: resident sequences raise every
    in-edge of their host, monotonically in the count; the trivial
    state is bit-identical to the serving-free matrix; migration is
    priced exactly at the link's communication model."""
    spec = _serving_spec(kv_weight=0.0)
    net, _ = generate.build_network(spec)
    base = net.cost_matrix().copy()

    net.kv_weight = 0.5
    net.invalidate_costs()
    assert not net.kv_active()
    # trivial state (no residents) must reproduce the base bytes
    assert np.array_equal(net.cost_matrix(), base)

    nid = sorted(net.nodes)[2]
    prev = base
    for count in (1, 3, 9):
        net.set_kv_residency(nid, count)
        assert net.kv_active()
        m = net.cost_matrix().copy()
        col = [i for i in sorted(net.nodes) if i != nid]
        # host column strictly more expensive, monotone in residency
        assert all(m[i, nid] > prev[i, nid] for i in col)
        assert np.isclose(m[3, nid] - base[3, nid],
                          net.kv_weight * count)
        # every other column untouched
        other = [j for j in sorted(net.nodes) if j != nid]
        assert np.array_equal(m[np.ix_(other, other)],
                              base[np.ix_(other, other)])
        prev = m

    # migration pays the same wire-codec physics as activations
    kv_bytes = 4096.0
    assert (net.kv_migration_cost(3, nid, kv_bytes)
            == net.comm_cost(3, nid, kv_bytes))

    # bulk clear snaps back to the trivial serving-free matrix
    net.update_kv_residency({})
    assert not net.kv_active()
    assert np.array_equal(net.cost_matrix(), base)


def test_continuous_batching_bit_match():
    """Same-stage stacked decode must be bit-identical to the
    standalone one-request-at-a-time serve path, while actually
    batching (more stacked rows than dispatches)."""
    spec = _serving_spec(gen_tokens=4, serve_batch=3, iterations=2)
    out = check_serving_consistency(spec)
    assert out["streams_checked"] >= 1
    assert out["summary"]["completed"] >= 1.0
    assert out["stacked_rows"] > out["decode_dispatches"], \
        "cohorts never stacked — continuous batching is not exercised"


@pytest.mark.scenarios
def test_crash_mid_decode_recovers_exact_stream():
    """A relay crash while requests are mid-decode: the defended
    executor requeues onto a surviving chain, teacher-force replays the
    generated prefix to rebuild the KV cache, and finishes the *exact*
    token streams of an undisturbed run — at far better tail latency
    than the undefended drop-and-retry baseline."""
    calm = _serving_spec()
    crash = dataclasses.replace(
        calm, churn=[{"kind": "trace", "events": [(0, "crash", 5, 0.45)]}])
    crash.validate()

    # sim: every victim is mid-decode (k > 0) when the relay dies
    eng = generate.build_serving_sim(crash)
    sim_ms = eng.run(crash.iterations)
    ks = [op[5] for tl in eng.traces for op in tl if op[0] == "requeue"]
    assert ks and all(k > 0 for k in ks), \
        f"crash must land mid-decode, requeue prefixes were {ks}"

    ref = generate.build_serving_runtime(calm)
    ref.run(calm.iterations)
    tr = generate.build_serving_runtime(crash)
    rt_ms = tr.run(crash.iterations)
    assert tr.replay_steps > 0, "requeue never replayed a KV prefix"
    assert [summarize_serving([m]) for m in rt_ms] \
        == [summarize_serving([m]) for m in sim_ms]
    for rid in range(3):
        assert tr.token_stream(rid) == ref.token_stream(rid), \
            f"request {rid} stream diverged after crash-requeue"

    und = generate.build_serving_runtime(crash, reroute=False)
    und_ms = und.run(crash.iterations)
    su = summarize_serving(und_ms)
    sd = summarize_serving(rt_ms)
    assert su["restarts"] >= 1.0 and sd["requeues"] >= 1.0
    assert su["p99_ttft"] > sd["p99_ttft"], \
        "defended requeue should beat drop-and-retry tail latency"
    for rid in range(3):      # undefended restarts are slow, not wrong
        assert und.token_stream(rid) == ref.token_stream(rid)
