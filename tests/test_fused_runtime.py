"""Fused residual-carrying dispatch, int8 activation codec, donation
gating, session kernel/param caches (the PR-7 runtime rework)."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.flow.graph import geo_distributed_network
from repro.core.runtime import cache
from repro.core.runtime.activations import (ActivationStore, Int8Codec,
                                            make_codec)
from repro.core.runtime.stages import StageCompute, _donate_supported
from repro.core.runtime.trainer import (CentralizedTrainer, RuntimeTrainer,
                                        auto_chunk)
from repro.core.sim.faults import TraceChurn
from repro.data.pipeline import DataConfig, DataNodeShard


def tiny_cfg():
    cfg = get_config("gwtf-llama-300m").reduced(num_layers=4, d_model=128)
    return dataclasses.replace(cfg, vocab_size=256)


def make_net(seed=0, stages=2):
    return geo_distributed_network(
        num_stages=stages, relay_capacities=[3] * (3 * stages),
        num_data_nodes=1, data_capacity=4,
        rng=np.random.default_rng(seed))


def make_mbs(cfg, seed=0):
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, batch_size=8,
                    microbatch_size=2, seed=seed)
    return DataNodeShard(dc, 0, 1).microbatches()


def tree_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# Fused vs remat: bit-equality per stage and per trainer
# ---------------------------------------------------------------------------

def test_fused_forward_and_backward_bitwise_match_remat(rng):
    """Per-stage oracle: the fused dispatch's primal output equals the
    plain forward bitwise, and the backward from stored residuals
    equals the rematerialising backward bitwise — with the dispatch
    counters telling the two modes apart."""
    cfg = tiny_cfg()
    S = 2
    stage_p, _ = cache.initial_params(cfg, S, 0)
    sc = StageCompute(cfg, S)
    x = jnp.asarray(rng.normal(size=(4, 16, cfg.d_model)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(4, 16, cfg.d_model)).astype(np.float32))

    out_plain = sc.forward(0, stage_p[0], x)
    out_fused, resid = sc.forward_fused(0, stage_p[0], x)
    assert np.array_equal(np.asarray(out_plain), np.asarray(out_fused))

    dp_f, dx_f = sc.backward_from_residuals(0, resid, jnp.copy(g))
    dp_r, dx_r = sc.backward(0, stage_p[0], x, jnp.copy(g))
    assert tree_equal(dp_f, dp_r)
    assert np.array_equal(np.asarray(dx_f), np.asarray(dx_r))

    assert sc.fwd_calls[0] == 2          # plain + fused
    assert sc.bwd_calls[0] == 2          # residual + remat
    assert sc.remat_recomputes[0] == 1   # only the remat backward
    assert sc.stage_dispatches == 4


def test_fused_and_remat_trainers_bit_identical():
    """Trainer-level oracle: ``remat=True`` (the fallback) and the
    default fused path produce bit-identical loss trajectories and
    final parameters, while only the remat path recomputes forwards."""
    cfg = tiny_cfg()
    mbs = make_mbs(cfg)
    dn = make_net().data_nodes()[0].id
    fused = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                           churn_model=TraceChurn([]))
    remat = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                           churn_model=TraceChurn([]), remat=True)
    for _ in range(3):
        rf = fused.iteration({dn: mbs})
        rr = remat.iteration({dn: mbs})
        assert rf.loss == rr.loss
    assert fused.stages.snapshot()["fwd"] == remat.stages.snapshot()["fwd"]
    assert fused.stages.snapshot()["bwd"] == remat.stages.snapshot()["bwd"]
    assert fused.stages.remat_recompute_count == 0
    assert remat.stages.remat_recompute_count == sum(
        remat.stages.bwd_calls)
    assert tree_equal(fused.stage_params, remat.stage_params)
    assert tree_equal(fused.head_params, remat.head_params)
    # the fused path keeps residuals resident; remat only boundaries
    assert fused.last_store_peak_bytes > remat.last_store_peak_bytes


def test_zero_churn_fused_bit_identical_to_centralized():
    cfg = tiny_cfg()
    mbs = make_mbs(cfg)
    dn = make_net().data_nodes()[0].id
    rt = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                        churn_model=TraceChurn([]))
    cen = CentralizedTrainer(cfg, 2, lr=3e-3, seed=0)
    for _ in range(2):
        r = rt.iteration({dn: mbs})
        assert r.loss == cen.iteration(mbs)
    assert tree_equal(rt.stage_params, cen.stage_params)


# ---------------------------------------------------------------------------
# int8 codec
# ---------------------------------------------------------------------------

def test_int8_roundtrip_error_bound(rng):
    """Elementwise |x - dq(q(x))| <= scale/2 for per-tensor symmetric
    quantisation with round-to-nearest."""
    codec = Int8Codec()
    for shape, scale_mag in [((64, 32), 1.0), ((8, 128), 37.5),
                             ((100,), 1e-4), ((3, 5, 7), 1e3)]:
        x = jnp.asarray(
            (rng.normal(size=shape) * scale_mag).astype(np.float32))
        enc = codec.encode(x)
        dq = codec.decode(enc)
        scale = float(jnp.max(jnp.abs(x))) / 127.0
        err = np.abs(np.asarray(x) - np.asarray(dq))
        assert err.max() <= scale / 2 + 1e-7 * max(1.0, scale_mag)
    # degenerate: all-zero tensor survives (scale fallback, exact)
    z = jnp.zeros((4, 4), jnp.float32)
    assert np.array_equal(np.asarray(codec.decode(codec.encode(z))),
                          np.zeros((4, 4), np.float32))
    # non-float leaves pass through untouched
    ints = jnp.arange(10, dtype=jnp.int32)
    assert codec.encode(ints) is ints


def test_int8_store_shrinks_resident_bytes(rng):
    """Boundary activations AND residual trees shrink ~4x (>= 3x with
    the fp32 scale overhead)."""
    x = jnp.asarray(rng.normal(size=(8, 64, 128)).astype(np.float32))
    resid = {"a": x * 2, "b": jnp.asarray(
        rng.normal(size=(4, 32, 128)).astype(np.float32)),
        "ids": jnp.arange(8, dtype=jnp.int32)}
    fp = ActivationStore()
    q8 = ActivationStore(codec="int8")
    for store in (fp, q8):
        store.put(0, (0, 1), x)
        store.put_residuals(0, (0, 1), resid)
    assert fp.nbytes() / q8.nbytes() >= 3.0
    # round-trip through the store stays within the codec bound
    got = q8.stacked(0, (0, 1))
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    assert float(jnp.max(jnp.abs(got - x))) <= scale / 2 + 1e-7
    r = q8.residuals(0, (0, 1))
    assert np.array_equal(np.asarray(r["ids"]), np.arange(8))
    # drop releases both boundary and residuals
    q8.drop(0, (0, 1))
    assert len(q8) == 0 and q8.nbytes() == 0
    assert q8.peak_bytes > 0


def test_int8_trainer_close_to_fp_and_3x_smaller():
    cfg = tiny_cfg()
    mbs = make_mbs(cfg)
    dn = make_net().data_nodes()[0].id
    fp = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                        churn_model=TraceChurn([]))
    q8 = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                        churn_model=TraceChurn([]), activation_codec="int8")
    for _ in range(3):
        rf = fp.iteration({dn: mbs})
        rq = q8.iteration({dn: mbs})
    assert rf.store_peak_bytes / rq.store_peak_bytes >= 3.0
    assert np.isfinite(rq.loss)
    assert abs(rq.loss - rf.loss) < 0.25      # bounded fidelity cost
    assert q8.losses[-1] < q8.losses[0]       # still trains


def test_unknown_codec_rejected():
    with pytest.raises(ValueError, match="unknown activation codec"):
        make_codec("fp8")


# ---------------------------------------------------------------------------
# Recovery replays from residuals: zero forward recompute
# ---------------------------------------------------------------------------

def test_backward_crash_replays_from_residuals_no_forward_recompute():
    """A backward crash on the fused path is repaired from the stored
    residuals: the extra work is backward dispatches only — forward
    counters and remat recomputes stay at the healthy baseline."""
    cfg = tiny_cfg()
    mbs = make_mbs(cfg, seed=1)
    base = RuntimeTrainer(cfg, make_net(1), lr=3e-3, seed=0,
                          churn_model=TraceChurn([]))
    dn = make_net(1).data_nodes()[0].id
    rb = base.iteration({dn: mbs})
    relay = base.last_resolution.completed[0].chain[2]
    hit = sum(1 for j in base.last_resolution.completed
              if j.chain[2] == relay)
    tr = RuntimeTrainer(cfg, make_net(1), lr=3e-3, seed=0,
                        churn_model=TraceChurn([(0, "crash", relay, 0.6)]))
    rt = tr.iteration({dn: mbs})
    assert rt.completed == rt.launched
    assert rt.bwd_replays == hit >= 1
    assert rt.loss == rb.loss
    b, t = base.stages, tr.stages
    # zero forward recompute: pinned via stage_dispatches split
    assert t.fwd_calls == b.fwd_calls
    assert t.remat_recompute_count == b.remat_recompute_count == 0
    assert sum(t.bwd_calls) - sum(b.bwd_calls) == hit
    assert t.stage_dispatches - b.stage_dispatches == hit


# ---------------------------------------------------------------------------
# Donation gating
# ---------------------------------------------------------------------------

def test_donate_supported_gating():
    assert _donate_supported("cpu") is False
    for b in ("gpu", "cuda", "rocm", "tpu"):
        assert _donate_supported(b) is True
    # default reflects the live backend
    assert _donate_supported() == (jax.default_backend()
                                   in ("gpu", "cuda", "rocm", "tpu"))


def test_both_donation_branches_identical_numerics(rng):
    """Force both donation branches (CPU ignores donation but compiles
    the donated program): identical numerics, no use-after-donate."""
    cfg = tiny_cfg()
    stage_p, _ = cache.initial_params(cfg, 2, 0)
    x = jnp.asarray(rng.normal(size=(4, 16, cfg.d_model)).astype(np.float32))
    g0 = rng.normal(size=(4, 16, cfg.d_model)).astype(np.float32)
    results = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # 'donation is not implemented'
        for donate in (False, True):
            sc = StageCompute(cfg, 2, donate=donate)
            assert sc.donate is donate
            out, resid = sc.forward_fused(0, stage_p[0], x)
            dp, dx = sc.backward_from_residuals(0, resid, jnp.asarray(g0))
            # residuals were NOT donated: a second replay (the crash
            # path) from the same stored residuals must still work
            dp2, dx2 = sc.backward_from_residuals(0, resid,
                                                  jnp.asarray(g0))
            assert tree_equal(dp, dp2) and tree_equal(dx, dx2)
            results[donate] = (out, dp, dx)
    for a, b in zip(results[False], results[True]):
        assert tree_equal(a, b)


# ---------------------------------------------------------------------------
# Session caches: shared kernels/params, no state leak across hits
# ---------------------------------------------------------------------------

def test_kernel_cache_shared_counters_isolated():
    cfg = tiny_cfg()
    sc1 = StageCompute(cfg, 2, donate=False)
    sc2 = StageCompute(cfg, 2, donate=False)
    assert sc1._k is sc2._k               # one compiled kernel set
    stage_p, head_p = cache.initial_params(cfg, 2, 0)
    toks = jnp.zeros((2, 8), jnp.int32)
    sc1.embed(head_p, toks)
    assert sc1.embed_calls == 1 and sc2.embed_calls == 0


def test_param_cache_hit_does_not_leak_training_state(runtime_env):
    """Train a cached-init trainer, then check a fresh cache hit still
    hands out the pristine initial parameters."""
    cfg, S = runtime_env["cfg"], runtime_env["stages"]
    before = jax.tree.map(lambda a: np.asarray(a).copy(),
                          cache.initial_params(cfg, S, 0))
    mbs = make_mbs(cfg)
    dn = make_net().data_nodes()[0].id
    tr = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                        churn_model=TraceChurn([]))
    tr.iteration({dn: mbs})
    after = cache.initial_params(cfg, S, 0)
    assert tree_equal(before, after)
    # trained params did move (the trainer replaced, not mutated)
    assert not tree_equal(tr.stage_params, list(after[0]))
    info = cache.cache_info()
    assert info["initial_params"]["hits"] >= 1


# ---------------------------------------------------------------------------
# Dispatch chunking
# ---------------------------------------------------------------------------

def test_auto_chunk_rule():
    # small microbatches stack up to the cap...
    assert auto_chunk(32, 1, 32, 128) == 4
    assert auto_chunk(2, 1, 32, 128) == 2
    # ...huge ones fall back to per-microbatch dispatch
    assert auto_chunk(8, 2, 512, 512) == 1
    assert auto_chunk(0, 1, 32, 128) >= 1


def test_dispatch_chunk_override_keeps_trainers_bit_identical():
    cfg = tiny_cfg()
    mbs = make_mbs(cfg)                       # 4 microbatches
    dn = make_net().data_nodes()[0].id
    rt = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                        churn_model=TraceChurn([]), dispatch_chunk=2)
    cen = CentralizedTrainer(cfg, 2, lr=3e-3, seed=0, dispatch_chunk=2)
    r = rt.iteration({dn: mbs})
    assert r.loss == cen.iteration(mbs)
    assert rt.stages.fwd_calls == [2, 2]      # 4 mbs / chunks of 2
    assert cen.stages.fwd_calls == [2, 2]


# ---------------------------------------------------------------------------
# Loss read order inside a chunk
# ---------------------------------------------------------------------------

class _RecordingStages:
    """`StageCompute` proxy that logs each dispatch by method name and
    keeps the per-microbatch losses every ``head_loss`` returned."""

    def __init__(self, inner, log):
        self._inner, self._log = inner, log
        self.head_losses = []

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if not callable(attr):
            return attr

        def call(*args, **kwargs):
            self._log.append(("stage", name))
            out = attr(*args, **kwargs)
            if name == "head_loss":
                self.head_losses.append(out[0])
            return out
        return call


class _RecordingJnp:
    """``jax.numpy`` as the trainer module sees it, logging ``sum``."""

    def __init__(self, log):
        self._log = log

    def __getattr__(self, name):
        return getattr(jnp, name)

    def sum(self, *args, **kwargs):
        self._log.append(("jnp", "sum"))
        return jnp.sum(*args, **kwargs)


@pytest.mark.parametrize("remat", [False, True], ids=["fused", "remat"])
def test_chunk_loss_reduced_after_head_and_read_after_backward(
        monkeypatch, remat):
    """Per chunk, the loss sum is dispatched right after the head and
    before the first backward, and the one blocking read
    (``gwtf.loss_sync``) comes after the last backward and the
    embedding pull-back; the value read equals ``float(jnp.sum(losses))``
    of the head's losses bit for bit."""
    from repro.core.runtime import trainer as trainer_mod

    log = []

    class _RecordingSpan:
        def __init__(self, name, **kwargs):
            self.name = name

        def __enter__(self):
            log.append(("span", self.name))
            return self

        def __exit__(self, *exc):
            return False

    returned = []
    chunk_pass = trainer_mod._chunk_pass

    def recording_chunk_pass(*args, **kwargs):
        out = chunk_pass(*args, **kwargs)
        returned.append(out[0])
        return out

    monkeypatch.setattr(trainer_mod, "TraceAnnotation", _RecordingSpan)
    monkeypatch.setattr(trainer_mod, "jnp", _RecordingJnp(log))
    monkeypatch.setattr(trainer_mod, "_chunk_pass", recording_chunk_pass)

    cfg = tiny_cfg()
    mbs = make_mbs(cfg)                       # 4 microbatches
    dn = make_net().data_nodes()[0].id
    rt = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0, remat=remat,
                        churn_model=TraceChurn([]), dispatch_chunk=2)
    stages = _RecordingStages(rt.stages, log)
    rt.stages = stages
    r = rt.iteration({dn: mbs})

    backward = "backward" if remat else "backward_from_residuals"
    starts = [i for i, e in enumerate(log) if e == ("span", "gwtf.chunk")]
    assert len(starts) == 2 == r.host_syncs
    for lo, hi in zip(starts, starts[1:] + [len(log)]):
        chunk = log[lo:hi]
        head = chunk.index(("stage", "head_loss"))
        sums = [i for i, e in enumerate(chunk) if e == ("jnp", "sum")]
        bwds = [i for i, e in enumerate(chunk) if e == ("stage", backward)]
        embed_bwd = chunk.index(("stage", "embed_backward"))
        syncs = [i for i, e in enumerate(chunk)
                 if e == ("span", "gwtf.loss_sync")]
        assert len(sums) == 1 and len(syncs) == 1 and len(bwds) == 2
        assert head < sums[0] < bwds[0]
        assert syncs[0] > max(bwds[-1], embed_bwd)

    assert len(returned) == len(stages.head_losses) == 2
    for got, losses in zip(returned, stages.head_losses):
        assert got == float(jnp.sum(losses))
    assert r.loss == sum(returned) / len(mbs)
