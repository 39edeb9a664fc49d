"""Compression-aware WAN planning: per-link codec pricing in the flow
layer, bytes-on-wire accounting in the simulator, and bf16/top-k wire
codecs on the runtime's inter-stage boundary transfers (the PR-8
compression rework).  The fp32-only menu must be a bit-exact no-op on
every layer."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.flow.graph import (WIRE_CODECS, FlowNetwork,
                                   geo_distributed_network)
from repro.core.runtime.activations import (Bf16Codec, TopKCodec,
                                            make_codec)
from repro.core.runtime.trainer import CentralizedTrainer, RuntimeTrainer
from repro.core.scenarios import generate
from repro.core.scenarios.spec import ScenarioSpec
from repro.core.sim.faults import TraceChurn
from repro.data.pipeline import DataConfig, DataNodeShard
from tests._hypothesis_compat import given, settings, st

FULL_MENU = ("fp32", "bf16", "int8", "top-k")


def make_net(seed=0, stages=2, **kw):
    return geo_distributed_network(
        num_stages=stages, relay_capacities=[3] * (3 * stages),
        num_data_nodes=1, data_capacity=4,
        rng=np.random.default_rng(seed), **kw)


def geo_spec(**kw):
    base = dict(name="t", seed=7, topology="geo", num_stages=3,
                relays_per_stage=3, num_data_nodes=1, data_capacity=3,
                num_locations=4, iterations=2)
    base.update(kw)
    return ScenarioSpec(**base).validate()


# ---------------------------------------------------------------------------
# Flow layer: codec-aware link pricing
# ---------------------------------------------------------------------------

class TestFlowCodecPricing:
    def test_fp32_menu_is_bit_identical_to_default(self):
        """The default menu and an explicit fp32-only menu produce the
        exact same cached matrices and scalar costs (the codec
        machinery's off switch is bit-exact, not approximately so)."""
        a = make_net(seed=3)
        b = make_net(seed=3)
        b.codec_menu = ("fp32",)
        b.fidelity_budget = 0.5          # budget is irrelevant to fp32
        np.testing.assert_array_equal(a.cost_matrix(), b.cost_matrix())
        for size in (None, 1.0, 4096.0, a.activation_size):
            np.testing.assert_array_equal(a.edge_matrix(size),
                                          b.edge_matrix(size))
            if size is not None:
                np.testing.assert_array_equal(a.comm_matrix(size),
                                              b.comm_matrix(size))
            assert a.edge_cost(0, 3, size) == b.edge_cost(0, 3, size)
            assert a.comm_cost(2, 4, size) == b.comm_cost(2, 4, size)
        assert (b.wire_codec_matrix() == 0).all()

    def test_budget_gates_admissibility(self):
        net = make_net()
        net.codec_menu = FULL_MENU
        net.fidelity_budget = 0.0
        assert net.wire_codec_names() == ("fp32",)   # all lossy codecs out
        net.fidelity_budget = 0.02
        assert net.wire_codec_names() == ("fp32", "bf16", "int8")
        net.fidelity_budget = 1.0
        assert net.wire_codec_names() == FULL_MENU

    def test_unknown_codec_name_rejected(self):
        net = make_net()
        net.codec_menu = ("fp32", "fp64")
        with pytest.raises(ValueError, match="unknown wire codec"):
            net.cost_matrix()

    def test_choice_is_per_edge_price_argmin(self):
        """Every entry of the codec-choice matrix is the true scalar
        argmin of the per-codec edge price (first-min tie-break), and
        the priced matrix equals the chosen codec's price."""
        net = make_net(seed=5)
        net.codec_menu = FULL_MENU
        net.fidelity_budget = 0.1
        size = net.activation_size / 64.0   # small enough to diversify
        comm = net.comm_matrix(size)
        choice = net.wire_codec_matrix(size)
        adm = net.admissible_codecs()
        lat = 0.5 * (net.latency + net.latency.T)
        bw = net.bandwidth + net.bandwidth.T
        n = lat.shape[0]
        rng = np.random.default_rng(0)
        for i, j in zip(rng.integers(0, n, 40), rng.integers(0, n, 40)):
            prices = [lat[i, j] + 2.0 * (c.ratio * size) / bw[i, j]
                      + c.coder_rate * size
                      + net.fidelity_weight * c.fidelity_penalty
                      for c in adm]
            k = int(np.argmin(prices))
            assert choice[i, j] == k
            assert comm[i, j] == pytest.approx(prices[k], rel=1e-12)

    def test_wan_links_compress_fast_links_do_not(self):
        """Co-optimization story: at a payload size where transfer time
        matters, slow inter-location links pick an aggressive codec
        while at a tiny payload every link stays fp32 (the fidelity
        penalty dominates)."""
        net = make_net(seed=1)
        net.codec_menu = FULL_MENU
        net.fidelity_budget = 0.1
        big = net.wire_codec_matrix(net.activation_size)
        assert (big > 0).any()               # someone compressed
        tiny = net.wire_codec_matrix(1.0)
        assert (tiny == 0).all()             # nobody compresses 1 byte

    def test_flow_records_chosen_codecs(self):
        spec = geo_spec(compression={"menu": list(FULL_MENU),
                                     "fidelity_budget": 0.1})
        flow = generate.run_flow(spec)
        codecs = flow.protocol.flow_codecs()
        assert len(codecs) == len(flow.flows)
        names = set(flow.net.wire_codec_names())
        for chain, chain_codecs in zip(flow.flows, codecs):
            assert len(chain_codecs) == len(chain) - 1
            assert set(chain_codecs) <= names

    def test_matrix_cache_survives_alternating_sizes(self):
        """Regression for the single-entry per-size cache: alternating
        comm/edge matrix sizes must hit the per-epoch dict, not rebuild
        every call."""
        net = make_net(seed=2)
        for _ in range(100):
            net.comm_matrix(1024.0)
            net.comm_matrix(net.activation_size)
            net.edge_matrix(1024.0)
            net.edge_matrix(net.activation_size)
        assert net.matrix_rebuild_count <= 4
        # same behaviour with a non-trivial menu
        net.codec_menu = FULL_MENU
        net.fidelity_budget = 0.1
        base = net.matrix_rebuild_count
        for _ in range(100):
            net.comm_matrix(1024.0)
            net.comm_matrix(net.activation_size)
        assert net.matrix_rebuild_count - base <= 2
        # a cost-epoch bump invalidates and rebuilds once per size
        net.invalidate_costs()
        base = net.matrix_rebuild_count
        for _ in range(10):
            net.comm_matrix(1024.0)
        assert net.matrix_rebuild_count - base == 1


# ---------------------------------------------------------------------------
# Runtime codecs: bf16 and top-k round-trip bounds (property tests)
# ---------------------------------------------------------------------------

class TestBf16Codec:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10 ** 6), rows=st.integers(1, 8),
           cols=st.integers(1, 64),
           mag=st.floats(1e-4, 1e4))
    def test_roundtrip_relative_error_bound(self, seed, rows, cols, mag):
        """Elementwise |x - dq(q(x))| <= 2**-8 * |x| (half an ulp of
        bf16's eps = 2**-7) for normal values."""
        rng = np.random.default_rng(seed)
        x = jnp.asarray((rng.normal(size=(rows, cols)) * mag
                         ).astype(np.float32))
        codec = Bf16Codec()
        enc = codec.encode(x)
        dq = np.asarray(codec.decode(enc))
        assert dq.dtype == np.float32
        err = np.abs(np.asarray(x) - dq)
        assert (err <= 2.0 ** -8 * np.abs(np.asarray(x)) + 1e-30).all()
        assert codec.nbytes(enc) * 2 == x.nbytes

    def test_non_float_passthrough(self):
        codec = Bf16Codec()
        ids = jnp.arange(12, dtype=jnp.int32)
        assert codec.encode(ids) is ids
        assert codec.decode(ids) is ids


class TestTopKCodec:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10 ** 6), n=st.integers(8, 512),
           k_frac=st.floats(0.05, 1.0))
    def test_roundtrip_error_bounded_by_min_kept(self, seed, n, k_frac):
        """Kept entries round-trip exactly; every dropped magnitude is
        <= the smallest kept magnitude."""
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
        codec = TopKCodec(k_frac=k_frac)
        enc = codec.encode(x)
        dq = np.asarray(codec.decode(enc))
        kept = np.asarray(enc.idx)
        np.testing.assert_array_equal(dq[kept], np.asarray(x)[kept])
        dropped = np.setdiff1d(np.arange(n), kept)
        assert (dq[dropped] == 0).all()
        if dropped.size:
            min_kept = np.abs(np.asarray(enc.vals)).min()
            assert np.abs(np.asarray(x)[dropped]).max() <= min_kept
        err = np.abs(np.asarray(x) - dq)
        bound = np.abs(np.asarray(enc.vals)).min()
        assert err.max() <= bound + 1e-30

    def test_nbytes_monotone_in_k(self, rng):
        x = jnp.asarray(rng.normal(size=(32, 32)).astype(np.float32))
        sizes = [TopKCodec(k_frac=k).nbytes(TopKCodec(k_frac=k).encode(x))
                 for k in (0.05, 0.1, 0.25, 0.5, 1.0)]
        assert sizes == sorted(sizes)
        assert sizes[0] < sizes[-1]

    def test_k_frac_validated(self):
        with pytest.raises(ValueError, match="k_frac"):
            TopKCodec(k_frac=0.0)
        with pytest.raises(ValueError, match="k_frac"):
            TopKCodec(k_frac=1.5)

    def test_shape_and_dtype_restored(self, rng):
        x = jnp.asarray(rng.normal(size=(3, 5, 7)).astype(np.float32))
        codec = TopKCodec(k_frac=0.25)
        dq = codec.decode(codec.encode(x))
        assert dq.shape == x.shape and dq.dtype == x.dtype


class TestCodecRegistry:
    def test_planner_names_resolve(self):
        """Every flow-layer WIRE_CODECS name maps onto a runtime codec
        (the alias table keeps the two registries in sync)."""
        from repro.core.runtime.activations import (CODEC_ALIASES, CODECS,
                                                    Int8Codec, NullCodec)
        for name in WIRE_CODECS:
            codec = make_codec(name)
            assert codec is not None
        assert isinstance(make_codec("fp32"), NullCodec)
        assert isinstance(make_codec("top-k"), TopKCodec)
        assert isinstance(make_codec("int8"), Int8Codec)
        assert set(CODEC_ALIASES.values()) <= set(CODECS)


# ---------------------------------------------------------------------------
# Sim layer: bytes-on-wire accounting
# ---------------------------------------------------------------------------

class TestSimBytesOnWire:
    def test_trivial_menu_counts_raw_bytes(self):
        spec = geo_spec()
        sim = generate.build_sim(spec)
        for m in sim.run(2):
            assert m.codec_legs is None
            assert m.bytes_on_wire > 0
            assert m.bytes_on_wire % sim.profile.activation_bytes == 0

    def test_codec_menu_shrinks_bytes_on_wire(self):
        base = geo_spec()
        comp = base.replace(compression={"menu": list(FULL_MENU),
                                         "fidelity_budget": 0.1})
        mb = generate.run_sim(base)
        mc = generate.run_sim(comp)
        raw = sum(m.bytes_on_wire for m in mb)
        enc = sum(m.bytes_on_wire for m in mc)
        assert enc < raw                    # compression actually helps
        assert raw / enc >= 2.0             # at least bf16 everywhere
        # a bandwidth-starved WAN pushes links to top-k (>= 3x is the
        # committed bench_sim gate on the WAN row)
        slow = base.replace(min_bandwidth=2e6, max_bandwidth=1e7,
                            compression=comp.compression)
        sraw = sum(m.bytes_on_wire
                   for m in generate.run_sim(slow.replace(
                       compression=None)))
        senc = sum(m.bytes_on_wire for m in generate.run_sim(slow))
        assert sraw / senc >= 3.0
        sim = generate.build_sim(comp)
        ratios = {c.name: c.ratio
                  for c in sim.net.admissible_codecs()}
        act = sim.profile.activation_bytes
        for m in sim.run(2):
            assert m.codec_legs and set(m.codec_legs) <= set(ratios)
            expect = sum(cnt * ratios[n] * act
                         for n, cnt in m.codec_legs.items())
            assert m.bytes_on_wire == pytest.approx(expect, rel=1e-9)

    def test_fp32_menu_summary_bit_identical(self):
        from repro.core.sim.metrics import summarize
        base = geo_spec(seed=9)
        fp32 = base.replace(compression={"menu": ["fp32"]})
        assert summarize(generate.run_sim(fp32)) == \
            summarize(generate.run_sim(base))


# ---------------------------------------------------------------------------
# Runtime layer: wire codecs on inter-stage boundary transfers
# ---------------------------------------------------------------------------

def tiny_cfg():
    cfg = get_config("gwtf-llama-300m").reduced(num_layers=4, d_model=128)
    return dataclasses.replace(cfg, vocab_size=256)


def make_mbs(cfg, seed=0):
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, batch_size=8,
                    microbatch_size=2, seed=seed)
    return DataNodeShard(dc, 0, 1).microbatches()


# Forced wire codecs on a seeded churn-free run of 16 microbatches over
# two stages: the end-of-run loss delta from the fp32 wire stays under a
# ceiling that catches a broken encode/decode pair, not quantisation
# drift, and the boundary bytes shrink at least by the codec's floor.
WIRE_LOSS_DELTA_MAX = {"bf16": 0.05, "int8": 0.5, "top-k": 2.5}
WIRE_BYTES_REDUCTION_MIN = {"bf16": 1.9, "int8": 3.0, "top-k": 6.0}
WIRE_D_MODEL, WIRE_SEQ, WIRE_MICROBATCHES, WIRE_ITERATIONS = 128, 64, 16, 4


def _wire_run(codec):
    """(last loss, last iteration's boundary bytes, its completed
    microbatches) of ``WIRE_ITERATIONS`` iterations with ``codec`` forced
    on the wire."""
    cfg = dataclasses.replace(
        get_config("gwtf-llama-300m").reduced(num_layers=2,
                                              d_model=WIRE_D_MODEL),
        vocab_size=512)
    net = geo_distributed_network(
        num_stages=2, relay_capacities=[16] * 6, num_data_nodes=1,
        data_capacity=WIRE_MICROBATCHES, rng=np.random.default_rng(0))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=WIRE_SEQ,
                    batch_size=WIRE_MICROBATCHES, microbatch_size=1, seed=0)
    mbs = DataNodeShard(dc, 0, 1).microbatches()
    t = RuntimeTrainer(cfg, net, lr=1e-3, seed=0, churn_model=TraceChurn([]),
                       wire_codec=codec)
    for _ in range(WIRE_ITERATIONS):
        r = t.iteration({0: mbs})
    return float(r.loss), t.last_wire_bytes, r.completed


@pytest.fixture(scope="module")
def fp32_wire_run():
    return _wire_run("fp32")


class TestRuntimeWire:
    @pytest.mark.parametrize("codec", ["bf16", "int8", "top-k"])
    def test_forced_wire_codec_bounded_loss_delta_and_bytes(
            self, fp32_wire_run, codec):
        fp_loss, fp_bytes, completed = fp32_wire_run
        loss, wire_bytes, _ = _wire_run(codec)
        assert fp_bytes == 0
        # every completed microbatch crosses the one boundary as fp32 rows
        raw = completed * WIRE_SEQ * WIRE_D_MODEL * 4
        assert raw / wire_bytes >= WIRE_BYTES_REDUCTION_MIN[codec]
        assert abs(loss - fp_loss) <= WIRE_LOSS_DELTA_MAX[codec]

    def test_forced_bf16_wire_bytes_and_bounded_loss_delta(self):
        cfg = tiny_cfg()
        mbs = make_mbs(cfg)
        dn = make_net().data_nodes()[0].id
        fp = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                            churn_model=TraceChurn([]))
        bf = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                            churn_model=TraceChurn([]), wire_codec="bf16")
        for _ in range(2):
            rf = fp.iteration({dn: mbs})
            rb = bf.iteration({dn: mbs})
        assert rf.wire_bytes == 0 and rf.wire_codecs == ()
        assert rb.wire_codecs == ("bf16",)
        # one boundary (S=2), forward only, bf16 = 2 bytes/element
        expect = rb.completed * 2 * 64 * cfg.d_model * 2
        assert rb.wire_bytes == expect
        assert np.isfinite(rb.loss)
        assert abs(rb.loss - rf.loss) < 0.1
        assert bf.losses[-1] < bf.losses[0]          # still trains

    def test_wire_codec_byte_ordering(self):
        """bf16 > int8 > top-k encoded bytes on the same transfers."""
        cfg = tiny_cfg()
        mbs = make_mbs(cfg)
        dn = make_net().data_nodes()[0].id
        got = {}
        for codec in ("bf16", "int8", "top-k"):
            t = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                               churn_model=TraceChurn([]),
                               wire_codec=codec)
            r = t.iteration({dn: mbs})
            got[codec] = r.wire_bytes
            assert np.isfinite(r.loss)
        assert got["bf16"] > got["int8"] > got["top-k"] > 0

    def test_bf16_wire_zero_churn_matches_centralized(self):
        """The wire is applied identically by both trainers: a forced
        elementwise codec keeps the zero-churn decentralized run
        bit-identical to `CentralizedTrainer` with the same codec."""
        cfg = tiny_cfg()
        mbs = make_mbs(cfg)
        dn = make_net().data_nodes()[0].id
        rt = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                            churn_model=TraceChurn([]), wire_codec="bf16")
        cen = CentralizedTrainer(cfg, 2, lr=3e-3, seed=0,
                                 wire_codec="bf16")
        for _ in range(2):
            r = rt.iteration({dn: mbs})
            assert r.loss == cen.iteration(mbs)
        assert cen.last_wire_bytes > 0
        assert cen.last_wire_bytes == rt.last_wire_bytes

    def test_planner_mode_follows_choice_matrix(self):
        cfg = tiny_cfg()
        mbs = make_mbs(cfg)
        net = make_net()
        net.codec_menu = FULL_MENU
        net.fidelity_budget = 0.1
        dn = net.data_nodes()[0].id
        t = RuntimeTrainer(cfg, net, lr=3e-3, seed=0,
                           churn_model=TraceChurn([]),
                           wire_codec="planner")
        r = t.iteration({dn: mbs})
        # geo default activation size: every WAN link prefers top-k
        assert r.wire_codecs == ("top-k",)
        assert r.wire_bytes > 0
        assert np.isfinite(r.loss)

    def test_planner_mode_with_fp32_menu_is_exact(self):
        """fp32-only menu + planner mode constructs no wire at all —
        bit-identical to a trainer with no wire codec."""
        cfg = tiny_cfg()
        mbs = make_mbs(cfg)
        dn = make_net().data_nodes()[0].id
        plain = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                               churn_model=TraceChurn([]))
        planner = RuntimeTrainer(cfg, make_net(), lr=3e-3, seed=0,
                                 churn_model=TraceChurn([]),
                                 wire_codec="planner")
        for _ in range(2):
            rp = plain.iteration({dn: mbs})
            rq = planner.iteration({dn: mbs})
            assert rq.loss == rp.loss
            assert rq.wire_bytes == 0 and rq.wire_codecs == ()
