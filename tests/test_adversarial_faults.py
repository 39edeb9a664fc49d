"""Beyond-fail-stop fault layer: adversarial fault models, the shared
fault timeline, and the reputation/quarantine pricing in Eq. 1.

The cross-layer contracts (sim timeline == runtime timeline, exact
screen precision/recall) are enforced by the scenario harness
(`tests/test_scenarios.py::TestAdversarialTier`); this file unit-tests
the building blocks plus the runtime gradient screen end-to-end on the
contamination regimes the harness cannot sweep cheaply.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.flow.graph import (QUARANTINE_THRESHOLD, REPORT_DROP,
                                   REPUTATION_FLOOR,
                                   geo_distributed_network)
from repro.core.sim.faults import (AdversarialPlan, ComposedChurn,
                                   CorruptGradientChurn, FlakyLinkChurn,
                                   StragglerChurn, adversarial_plan)
from repro.core.sim.timeline import (CROSS_LAYER_FAULTS, FaultRecord,
                                     FaultTimeline, record_injections)


# ---------------------------------------------------------------------------
# Fault-model construction + window semantics (numpy-only)
# ---------------------------------------------------------------------------

class TestFaultModelValidation:
    def test_straggler_rejects_speedups(self):
        with pytest.raises(ValueError, match=">= 1"):
            StragglerChurn({3: 0.5})

    def test_straggler_rejects_unknown_nodes(self):
        with pytest.raises(ValueError, match="unknown node 9"):
            StragglerChurn({9: 2.0}, known_ids=[0, 1, 2])
        with pytest.raises(ValueError, match="unknown node 9"):
            StragglerChurn(hangs=[9], known_ids=[0, 1, 2])

    def test_corrupt_rejects_bad_mode_scale_empty(self):
        with pytest.raises(ValueError, match="unknown corruption mode"):
            CorruptGradientChurn([1], mode="invert")
        with pytest.raises(ValueError, match="scale must be positive"):
            CorruptGradientChurn([1], scale=0.0)
        with pytest.raises(ValueError, match=">= 1 node"):
            CorruptGradientChurn([])

    def test_flaky_rejects_bad_probability(self):
        with pytest.raises(ValueError, match=r"\[0, 1\]"):
            FlakyLinkChurn(1.5)


class TestFaultWindows:
    def test_window_is_half_open(self):
        m = StragglerChurn({1: 2.0}, at_iteration=1, duration=2)
        assert [m.active(i) for i in range(5)] == \
               [False, True, True, False, False]
        assert adversarial_plan(m, 0) is None
        assert adversarial_plan(m, 1).slow == {1: 2.0}
        assert adversarial_plan(m, 3) is None

    def test_duration_zero_means_forever(self):
        m = CorruptGradientChurn([2], at_iteration=1)
        assert not m.active(0)
        assert m.active(10_000)

    def test_sample_draws_nothing(self):
        """Adversarial models publish plans via the side channel only;
        their sample() crashes nobody and never touches the shared
        churn RNG (that is what keeps fail-stop RNG streams identical
        with and without an adversarial clause)."""
        for m in (StragglerChurn({1: 2.0}), CorruptGradientChurn([1]),
                  FlakyLinkChurn(0.3)):
            assert m.sample(None) == {}


class TestPlanComposition:
    def test_merge_compounds_and_unions(self):
        a = AdversarialPlan(slow={1: 2.0, 2: 3.0}, hung=frozenset({4}),
                            corrupt={5: ("perturb", 1.0, 7)})
        b = AdversarialPlan(slow={1: 4.0}, hung=frozenset({6}),
                            corrupt={5: ("zero", 2.0, 9)},
                            flaky=(FlakyLinkChurn(0.1),))
        m = AdversarialPlan.merge([a, None, b])
        assert m.slow == {1: 8.0, 2: 3.0}        # slowdowns compound
        assert m.hung == {4, 6}
        assert m.corrupt[5] == ("perturb", 1.0, 7)   # first model wins
        assert m.flaky_episodes == 1

    def test_merge_of_nothing_is_none(self):
        assert AdversarialPlan.merge([]) is None
        assert AdversarialPlan.merge([None, AdversarialPlan()]) is None

    def test_composed_churn_exposes_merged_plan(self):
        model = ComposedChurn([
            StragglerChurn({1: 2.0}),
            CorruptGradientChurn([3], mode="perturb", seed=5),
            FlakyLinkChurn(0.2, at_iteration=1),
        ])
        p0 = adversarial_plan(model, 0)
        assert p0.slow == {1: 2.0}
        assert set(p0.corrupt) == {3}
        assert p0.flaky_episodes == 0           # window not open yet
        assert adversarial_plan(model, 1).flaky_episodes == 1


class TestFlakyDeterminism:
    def test_counter_based_coins_are_order_independent(self):
        m = FlakyLinkChurn(0.5, seed=3)
        keys = [(0, mb, d, pos, att) for mb in range(4)
                for d in ("fwd", "bwd") for pos in range(3)
                for att in range(2)]
        first = [m.leg_ok(*k) for k in keys]
        # evaluate in reverse order, interleaved with unrelated draws:
        # every decision must be a pure function of its key
        rng = np.random.default_rng(0)
        second = []
        for k in reversed(keys):
            rng.uniform()
            second.append(m.leg_ok(*k))
        assert first == list(reversed(second))
        assert 0 < sum(first) < len(first)      # p=0.5 actually flips

    def test_probability_edges(self):
        assert FlakyLinkChurn(0.0).leg_ok(0, 0, "fwd", 0, 0)
        assert not FlakyLinkChurn(1.0).leg_ok(0, 0, "fwd", 0, 0)

    def test_attempts_reflip_independently(self):
        m = FlakyLinkChurn(0.5, seed=11)
        flips = {m.leg_ok(0, 0, "fwd", 0, att) for att in range(32)}
        assert flips == {True, False}


# ---------------------------------------------------------------------------
# Shared fault timeline (numpy-only)
# ---------------------------------------------------------------------------

class TestTimeline:
    def test_rejects_unknown_vocabulary(self):
        with pytest.raises(ValueError, match="unknown fault class"):
            FaultRecord(0, "gremlin", "injection")
        with pytest.raises(ValueError, match="unknown record kind"):
            FaultRecord(0, "crash", "suspicion")

    def test_comparable_counts_excludes_engine_local_kinds(self):
        tl = FaultTimeline()
        tl.record(0, "flaky_link", "injection")
        tl.record(0, "flaky_link", "detection", 4)
        tl.record(0, "crash", "injection", 1)
        tl.record(0, "crash", "detection", 1)
        tl.record(0, "straggler", "detection", 2)
        tl.record(1, "corrupt_gradient", "repair", 3)
        cmp = tl.comparable_counts()
        # all injections stay; detection/repair only for the
        # iteration-granular cross-layer faults
        assert cmp == {
            (0, "flaky_link", "injection"): 1,
            (0, "crash", "injection"): 1,
            (0, "straggler", "detection"): 1,
            (1, "corrupt_gradient", "repair"): 1,
        }
        assert set(CROSS_LAYER_FAULTS) == {"straggler", "corrupt_gradient"}

    def test_record_injections_is_deterministic(self):
        plan = AdversarialPlan(slow={5: 2.0}, hung=frozenset({5, 7}),
                               corrupt={2: ("zero", 1.0, 0)},
                               flaky=(FlakyLinkChurn(0.1),))
        a, b = FaultTimeline(), FaultTimeline()
        for tl in (a, b):
            record_injections(tl, 3, {9: 0.5, 1: 0.25}, plan)
        assert a.records == b.records
        assert a.counts() == {
            (3, "crash", "injection"): 2,
            (3, "straggler", "injection"): 2,   # slow ∪ hung = {5, 7}
            (3, "corrupt_gradient", "injection"): 1,
            (3, "flaky_link", "injection"): 1,
        }
        empty = FaultTimeline()
        record_injections(empty, 0, {}, None)
        assert len(empty) == 0


# ---------------------------------------------------------------------------
# Reputation pricing / quarantine / rehabilitation (flow layer, numpy-only)
# ---------------------------------------------------------------------------

def _net():
    return geo_distributed_network(
        num_stages=2, relay_capacities=[2] * 6, num_data_nodes=1,
        data_capacity=4, rng=np.random.default_rng(0))


class TestReputationPricing:
    def test_trivial_state_is_bit_identical_and_cached(self):
        net, fresh = _net(), _net()
        cm = net.cost_matrix()
        assert net.cost_matrix() is cm          # same cached object
        assert not net.reputation_active()
        np.testing.assert_array_equal(cm, fresh.cost_matrix())

    def test_report_prices_only_the_accused_column(self):
        net = _net()
        base = net.cost_matrix().copy()
        v0 = net.cost_version
        net.report_fault(3)
        assert net.cost_version > v0            # planners must refresh
        rep = net.reputation(3)
        assert rep == pytest.approx(REPORT_DROP)
        cm = net.cost_matrix()
        expect_pen = net.reputation_weight * (1.0 / rep - 1.0)
        np.testing.assert_allclose(cm[:, 3] - base[:, 3], expect_pen)
        others = [j for j in range(cm.shape[1]) if j != 3]
        np.testing.assert_array_equal(cm[:, others], base[:, others])

    def test_quarantine_threshold_and_floor(self):
        net = _net()
        net.set_reputation(3, QUARANTINE_THRESHOLD)
        assert not net.quarantined(3)           # threshold is exclusive
        net.report_fault(3)                     # 0.5 * 0.2 = 0.1
        assert net.quarantined(3)
        for _ in range(50):
            net.report_fault(3)
        assert net.reputation(3) == REPUTATION_FLOOR
        assert np.isfinite(net.cost_matrix()).all()

    def test_single_report_already_quarantines(self):
        net = _net()
        net.report_fault(3)
        assert net.reputation(3) == pytest.approx(REPORT_DROP)
        assert net.quarantined(3)               # 0.2 < 0.5

    def test_decay_rehabilitates_back_to_exact_trivial(self):
        net = _net()
        base = net.cost_matrix().copy()
        net.report_fault(3)
        net.report_fault(3)
        assert net.quarantined(3)
        saw_release = False
        for _ in range(100):
            net.decay_reputations()
            if not net.quarantined(3):
                saw_release = True
        assert saw_release
        # full rehabilitation snaps storage back to None: pricing is
        # the *exact* trivial arithmetic again, not merely close to it
        assert not net.reputation_active()
        np.testing.assert_array_equal(net.cost_matrix(), base)

    def test_quarantine_survives_crash_and_rejoin(self):
        """A node that rejoins mid-quarantine is still distrusted:
        reputation tracks identity, not liveness, so a byzantine relay
        cannot launder its record by bouncing."""
        net = _net()
        net.report_fault(3)
        net.report_fault(3)
        net.kill_node(3)
        assert not net.nodes[3].alive
        net.nodes[3].alive = True               # rejoin
        assert net.quarantined(3)
        assert net.reputation(3) == pytest.approx(REPORT_DROP ** 2)

    def test_set_reputation_validates(self):
        net = _net()
        with pytest.raises(ValueError, match="reputation"):
            net.set_reputation(3, 0.0)
        with pytest.raises(ValueError, match="reputation"):
            net.set_reputation(3, 1.5)
        net.set_reputation(3, 0.3)
        assert net.quarantined(3)


# ---------------------------------------------------------------------------
# Runtime gradient screen end-to-end (real compute)
# ---------------------------------------------------------------------------

def _byz_trainer(churn_model=None, grad_screen=None, caps=None):
    from repro.configs import get_config
    from repro.core.runtime.trainer import RuntimeTrainer

    cfg = dataclasses.replace(
        get_config("gwtf-llama-300m").reduced(num_layers=2, d_model=32),
        vocab_size=512)
    net = (geo_distributed_network(
        num_stages=2, relay_capacities=caps, num_data_nodes=1,
        data_capacity=4, rng=np.random.default_rng(0))
        if caps else _net())
    if churn_model is not None and not isinstance(churn_model, ComposedChurn):
        churn_model = churn_model(net)
    return RuntimeTrainer(cfg, net, lr=1e-3, seed=0,
                          churn_model=churn_model, grad_screen=grad_screen)


def _batches(batch_size: int = 4):
    from repro.data.pipeline import DataConfig, DataNodeShard

    dc = DataConfig(vocab_size=512, seq_len=16, batch_size=batch_size,
                    microbatch_size=1, seed=3)
    return {0: DataNodeShard(dc, 0, 1).microbatches()}


class TestRuntimeGradientScreen:
    def test_screen_survives_half_contamination(self):
        """Node 2 carries 2 of the 4 planned chains — exactly 50%
        contamination, the regime where an interpolated median mixes
        honest and poisoned norms.  The lower-median screen must flag
        exactly the corrupt contributions, accuse only node 2, drive
        it into quarantine, and let decay rehabilitate it afterwards."""
        tr = _byz_trainer(
            churn_model=lambda net: CorruptGradientChurn(
                [2], mode="perturb", scale=1.0, seed=7,
                known_ids=net.nodes.keys()),
            grad_screen=None)                   # auto-on
        batches = _batches()
        ever_quarantined = False
        for _ in range(4):
            tr.iteration(batches)
            ever_quarantined = ever_quarantined or tr.net.quarantined(2)
        counts = tr.timeline.counts()
        det = {(it, n) for r in tr.timeline.records
               for it, n in [(r.iteration, r.node)]
               if r.fault == "corrupt_gradient" and r.kind == "detection"}
        assert counts.get((0, "corrupt_gradient", "detection"), 0) == 2
        assert {n for _, n in det} == {2}       # precision: only node 2
        assert ever_quarantined
        # decay rehabilitation: fault-free iterations (the plan routed
        # around node 2) lift its reputation back over the threshold
        assert not tr.net.quarantined(2)
        assert tr.net.reputation(2) > QUARANTINE_THRESHOLD

    def test_zero_mode_caught_below_half_contamination(self):
        """Deflation attacks (zeroed gradients) sort *below* the lower
        median, so at exactly half contamination the reference norm is
        itself poisoned and the screen goes blind by design (documented
        boundary).  Strictly below half — capacity 1 pins node 2 to a
        single chain of the 4, 25% contamination — the lower median
        stays honest and the norm floor catches the zeroed
        contribution."""
        tr = _byz_trainer(
            churn_model=lambda net: CorruptGradientChurn(
                [2], mode="zero", scale=1.0, seed=7,
                known_ids=net.nodes.keys()),
            grad_screen=None, caps=[2, 1, 2, 2, 2, 2])
        r = tr.iteration(_batches())
        assert r.grads_flagged == 1
        det_nodes = {rec.node for rec in tr.timeline.records
                     if rec.fault == "corrupt_gradient"
                     and rec.kind == "detection"}
        assert det_nodes == {2}
        assert tr.net.quarantined(2)

    def test_quarantine_reroutes_flow_off_corrupt_node(self):
        tr = _byz_trainer(
            churn_model=lambda net: CorruptGradientChurn(
                [2], mode="perturb", scale=1.0, seed=7,
                known_ids=net.nodes.keys()),
            grad_screen=None)
        batches = _batches()
        tr.iteration(batches)                   # detection + reports
        assert tr.net.quarantined(2)
        tr.iteration(batches)                   # replanned
        chains = tr.policy.protocol.complete_flows()
        assert all(2 not in chain[1:-1] for chain in chains)
        # ...and the screen consequently finds nothing more to flag
        assert tr.timeline.counts().get(
            (1, "corrupt_gradient", "detection"), 0) == 0

    def test_forced_screen_is_bit_identical_when_clean(self):
        """grad_screen=True defers aggregation until the screen has
        seen every contribution; with nothing flagged it must rebuild
        the same jnp.add chain in the same job order — losses
        bit-identical to the inline per-microbatch path.  (Both runs
        pin batch_microbatches=False: an enabled screen forces the
        per-microbatch path anyway, and the batched path associates
        floats differently by construction.)"""
        batches = _batches()
        losses = {}
        for screen in (False, True):
            tr = _byz_trainer(grad_screen=screen)
            tr.batch_microbatches = False
            rs = [tr.iteration(batches) for _ in range(2)]
            losses[screen] = [float(r.loss) for r in rs]
            assert tr.timeline.counts() == {}
            assert all(r.grads_flagged == 0 for r in rs)
        assert losses[False] == losses[True]

    def test_screen_holds_end_loss_under_ceiling(self):
        """Over six iterations with node 2 (1 of 6 relays) corrupting
        every contribution it carries, the end-of-run loss of the
        screened run stays within 0.25 of the clean run's, while the
        unscreened run's moves further: the poison hurts, and the
        screen contains it, detecting and quarantining node 2."""
        def corrupt(net):
            return CorruptGradientChurn([2], mode="perturb", scale=1.0,
                                        seed=7, known_ids=net.nodes.keys())

        def run(churn_model, grad_screen):
            tr = _byz_trainer(churn_model=churn_model,
                              grad_screen=grad_screen)
            batches, quarantined = _batches(), False
            for _ in range(6):
                loss = float(tr.iteration(batches).loss)
                quarantined = quarantined or tr.net.quarantined(2)
            detections = sum(tr.timeline.counts(
                kinds=("detection",), faults=("corrupt_gradient",)).values())
            return loss, detections, quarantined

        clean, _, _ = run(None, None)
        undefended, _, _ = run(corrupt, False)
        defended, detections, quarantined = run(corrupt, None)
        assert abs(defended - clean) < 0.25 < abs(undefended - clean)
        assert detections > 0 and quarantined

    def test_screen_off_lets_poison_through(self):
        tr = _byz_trainer(
            churn_model=lambda net: CorruptGradientChurn(
                [2], mode="perturb", scale=1.0, seed=7,
                known_ids=net.nodes.keys()),
            grad_screen=False)
        r = tr.iteration(_batches())
        assert r.grads_flagged == 0
        assert not tr.net.reputation_active()
        assert tr.timeline.counts(kinds=("detection",)) == {}
