"""repro.comm: wire-format round trips, measured-vs-analytic byte counts,
word-wise bit packing, transport simulation, and FedSim wire mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # see tests/hypothesis_fallback.py
    from hypothesis_fallback import given, settings, st

from repro.comm import (HEADER_BYTES, CommLog, NetworkConfig,
                        SimulatedNetwork, make_blocktopk_codec,
                        make_dense32_codec, make_sign_codec, make_topk_codec,
                        make_wire_codec, measured_vs_analytic, parse_header)
from repro.comm.wire import pack_uint as wire_pack_uint
from repro.comm.wire import unpack_uint as wire_unpack_uint
from repro.configs.base import FedConfig
from repro.core.rounds import FedSim, mesh_wire_bytes
from repro.kernels import (pack_bits_ref, pack_uint_words, unpack_bits_ref,
                           unpack_uint_words)
from repro.data.synthetic import FederatedClassification
from repro.models import params as pdefs
from repro.models.convmixer import MLPConfig, mlp_defs, mlp_loss


def _vec(seed, d):
    return jnp.asarray(np.random.default_rng(seed).normal(size=d),
                       jnp.float32)


# -- codec round trips -------------------------------------------------------


CODECS = {
    "dense32": lambda: make_dense32_codec(),
    "topk": lambda: make_topk_codec(1 / 8),
    "blocktopk": lambda: make_blocktopk_codec(1 / 8, block=64),
    "sign": lambda: make_sign_codec(),
    "sign_block": lambda: make_sign_codec(block=32),
}


@pytest.mark.parametrize("name", list(CODECS))
@pytest.mark.parametrize("d", [8, 37, 100, 5000])
def test_roundtrip_bit_exact(name, d):
    """decode(encode(x)) == the dense compressor output, bit-for-bit."""
    codec = CODECS[name]()
    x = _vec(d, d)
    buf = codec.encode(x)
    assert buf.dtype == jnp.uint8
    assert buf.shape[0] == codec.nbytes(d)
    dec = codec.decode(buf, d)
    ref = codec.compressor.compress(x).reshape(-1)
    assert np.array_equal(np.asarray(dec), np.asarray(ref)), name


def test_roundtrip_with_exact_zeros():
    """sign(0) := +1 — the wire and the dense compressor must agree on it."""
    x = jnp.asarray([0.0, -1.0, 2.0, 0.0, -0.5, 0.25, 0.0, 3.0], jnp.float32)
    for name in ("sign", "topk", "blocktopk"):
        codec = CODECS[name]()
        dec = codec.decode(codec.encode(x), x.size)
        ref = codec.compressor.compress(x).reshape(-1)
        assert np.array_equal(np.asarray(dec), np.asarray(ref)), name


def test_roundtrip_under_jit_and_vmap():
    codec = make_topk_codec(1 / 4)
    d = 128
    xs = jnp.stack([_vec(i, d) for i in range(4)])
    f = jax.jit(jax.vmap(lambda x: codec.decode(codec.encode(x), d)))
    ref = jnp.stack([codec.compressor.compress(x) for x in xs])
    assert np.array_equal(np.asarray(f(xs)), np.asarray(ref))


def test_narrow_value_dtypes_roundtrip_through_wire_dtype():
    d = 256
    x = _vec(3, d)
    for vd in ("float16", "bfloat16"):
        codec = make_topk_codec(1 / 8, vd)
        assert not codec.exact
        dec = codec.decode(codec.encode(x), d)
        ref = codec.compressor.compress(x).astype(jnp.dtype(vd))
        assert np.array_equal(np.asarray(dec),
                              np.asarray(ref.astype(jnp.float32)))


def test_blocktopk_int8_quantization_bounded():
    d = 512
    x = _vec(4, d)
    codec = make_blocktopk_codec(1 / 8, block=128, value_dtype="int8")
    dec = codec.decode(codec.encode(x), d)
    ref = codec.compressor.compress(x)
    kept = np.asarray(ref) != 0
    err = np.abs(np.asarray(dec) - np.asarray(ref))[kept]
    # int8 vs per-block fp32 scale: error <= scale/2 = max|v|/254 per block
    assert err.max() <= float(jnp.max(jnp.abs(x))) / 254 + 1e-7


def test_header_parses():
    codec = make_blocktopk_codec(1 / 4, block=64)
    h = parse_header(codec.encode(_vec(0, 200)))
    assert h == {"codec": "blocktopk", "value_dtype": "float32", "d": 200,
                 "k": 16, "block": 64}
    with pytest.raises(ValueError):
        parse_header(jnp.zeros(HEADER_BYTES, jnp.uint8))


def test_make_wire_codec_registry():
    for name in ("dense32", "none", "topk", "blocktopk", "sign", "packedsign"):
        assert make_wire_codec(name, 1 / 8).name
    with pytest.raises(ValueError):
        make_wire_codec("randk")


# -- measured vs analytic bytes (paper Table 1) ------------------------------


@pytest.mark.parametrize("d", [1000, 11_200_000])
def test_measured_bytes_match_analytic_bits(d):
    """Measured wire bits match Table 1's analytic counts within the
    documented per-message header (and beat them where indices are packed
    below 32 bits)."""
    header_bits = 8 * HEADER_BYTES
    for name in ("dense32", "topk", "sign"):
        r = measured_vs_analytic(make_wire_codec(name, 1 / 64), d)
        # allow <=7 padding bits (sign packs d bits to whole bytes)
        assert 0 <= r["overhead_bits"] <= header_bits + 7, r
    r = measured_vs_analytic(make_wire_codec("blocktopk", 1 / 64), d)
    if d > 10_000:  # 11-bit packed indices beat the analytic 32-bit ones
        assert r["measured_bits"] < r["analytic_bits"]
    assert r["measured_bits"] <= r["analytic_bits"] + header_bits + 7


@pytest.mark.parametrize("d", [8, 100, 5000])
def test_sign_codec_bits_match_packbits_ref(d):
    """The sign codec's payload after the header and scale is exactly the
    1-bit oracle's packing of the sign bits (sign(0) := +1)."""
    x = _vec(d + 1, d).at[0].set(0.0)
    buf = make_sign_codec().encode(x)
    bits = (x >= 0).astype(jnp.uint8)
    pad = (-d) % 8
    want = pack_bits_ref(jnp.pad(bits, (0, pad)))
    assert np.array_equal(np.asarray(buf[HEADER_BYTES + 4:]),
                          np.asarray(want))


# -- word-wise bit packing ---------------------------------------------------


@pytest.mark.parametrize("n", [2048, 8192, 4096])
def test_bitpack_words_match_refs(n):
    bits = jnp.asarray(np.random.default_rng(n).integers(0, 2, n), jnp.uint8)
    packed = pack_uint_words(bits, 1)
    assert np.array_equal(np.asarray(packed), np.asarray(pack_bits_ref(bits)))
    assert np.array_equal(np.asarray(packed), np.packbits(np.asarray(bits)))
    assert np.array_equal(np.asarray(unpack_uint_words(packed, 1, n)),
                          np.asarray(bits))
    assert np.array_equal(np.asarray(unpack_bits_ref(packed)),
                          np.asarray(bits))


def _naive_pack(vals, nbits):
    """The bit-matrix formulation the word-wise paths must match."""
    shifts = np.arange(nbits - 1, -1, -1, dtype=np.uint64)
    bits = ((np.asarray(vals, np.uint64)[:, None] >> shifts) & 1)
    return np.packbits(bits.astype(np.uint8).reshape(-1))


@given(st.integers(1, 32), st.integers(1, 3000))
def test_pack_uint_roundtrip_all_widths(nbits, count):
    """Property: for every nbits in 1..32, the word-wise path is
    byte-identical to the bit-matrix oracle and inverts exactly."""
    rng = np.random.default_rng(nbits * 10007 + count)
    hi = min(2 ** nbits, 2 ** 32)
    vals = jnp.asarray(
        rng.integers(0, hi, count, dtype=np.uint64).astype(np.uint32))
    packed = pack_uint_words(vals, nbits)
    assert packed.dtype == jnp.uint8
    assert np.array_equal(np.asarray(packed), _naive_pack(vals, nbits)), (
        nbits, count)
    un = unpack_uint_words(packed, nbits, count)
    assert np.array_equal(np.asarray(un), np.asarray(vals)), (nbits, count)


@pytest.mark.parametrize("nbits", [1, 3, 8, 11, 17, 32])
def test_wire_pack_uint_matches_naive(nbits):
    """wire.pack_uint/unpack_uint: the bit-matrix oracle's bytes, the
    packed size, and an exact inverse."""
    rng = np.random.default_rng(nbits)
    count = 1357
    hi = min(2 ** nbits, 2 ** 32)
    vals = jnp.asarray(
        rng.integers(0, hi, count, dtype=np.uint64).astype(np.uint32))
    packed = wire_pack_uint(vals, nbits)
    assert np.array_equal(np.asarray(packed), _naive_pack(vals, nbits))
    assert packed.size == (count * nbits + 7) // 8
    assert np.array_equal(np.asarray(wire_unpack_uint(packed, nbits, count)),
                          np.asarray(vals))


def test_blocktopk_codec_encode_matches_selection():
    """blocktopk's encode is the compressor's selection packed: the same
    bytes as encoding ``select(x)``, and it decodes to ``compress(x)``."""
    d = 5000
    x = _vec(17, d)
    jc = make_blocktopk_codec(1 / 8, block=2048)
    b1 = jc.encode(x)
    b2 = jc.encode_from_selection(jc.compressor.select(x), d)
    assert np.array_equal(np.asarray(b1), np.asarray(b2))
    ref = jc.compressor.compress(x).reshape(-1)
    assert np.array_equal(np.asarray(jc.decode(b1, d)), np.asarray(ref))


# -- transport ---------------------------------------------------------------


def test_transport_metrics_smoke():
    net = SimulatedNetwork(NetworkConfig(seed=7), num_clients=20)
    log = CommLog()
    times = []
    for r in range(10):
        t = net.round([1, 5, 9, 13], uplink_bytes_per_client=125_000,
                      downlink_bytes_per_client=500_000, round_idx=r)
        assert t.round_time_s >= t.mean_client_time_s > 0
        assert t.uplink_bytes == 4 * 125_000
        assert t.downlink_bytes == 4 * 500_000
        assert t.slowest_client in (1, 5, 9, 13)
        log.add(t)
        times.append(t.round_time_s)
    assert log.rounds == 10 and log.total_bytes == 10 * 4 * 625_000
    # deterministic given (seed, round)
    again = net.round([1, 5, 9, 13], 125_000, 500_000, round_idx=0)
    assert again.round_time_s == times[0]
    # more bytes on the same links cannot be faster
    slower = net.round([1, 5, 9, 13], 10 * 125_000, 500_000, round_idx=0)
    assert slower.round_time_s > times[0]


def test_transport_empty_round():
    net = SimulatedNetwork(NetworkConfig(), num_clients=4)
    t = net.round([], 1000, 1000, 0)
    assert t.round_time_s == 0.0 and t.slowest_client == -1
    assert t.uplink_bytes == 0 and t.mean_client_time_s == 0.0


def test_network_requires_wire_mode():
    net = SimulatedNetwork(NetworkConfig(), num_clients=12)
    with pytest.raises(ValueError, match="wire"):
        FedSim(lambda p, b: mlp_loss(p, b, MC),
               FedConfig(algorithm="fedcams", num_clients=12), network=net)


def test_links_for_vectorized_matches_per_id_draw():
    """The batched cold-path draw + searchsorted warm path must reproduce
    the original per-client loop's stream bit-for-bit: one Generator
    keyed (seed, id), two normals, independent of participation order."""
    cfg = NetworkConfig(seed=5)
    net = SimulatedNetwork(cfg, 1000)
    idx = np.array([7, 3, 500, 3, 999, 0])
    up, down = net._links_for(idx)
    mu = -0.5 * cfg.bandwidth_sigma ** 2
    for i, c in enumerate(idx):
        raw = np.random.default_rng((cfg.seed, int(c))).normal(
            mu, cfg.bandwidth_sigma, 2)
        assert up[i] == cfg.uplink_mbps * 1e6 / 8.0 * np.exp(raw[0])
        assert down[i] == cfg.downlink_mbps * 1e6 / 8.0 * np.exp(raw[1])
    # warm path (everything cached) returns the same values
    up2, down2 = net._links_for(idx)
    assert np.array_equal(up, up2) and np.array_equal(down, down2)
    # a fresh network sharing the seed agrees, different arrival order
    net2 = SimulatedNetwork(cfg, 1000)
    up3, down3 = net2._links_for(np.array([999, 0]))
    assert up3[0] == up[4] and down3[1] == down[5]


def test_round_timing_quantiles():
    net = SimulatedNetwork(NetworkConfig(seed=2), 64)
    t = net.round(np.arange(64), 10_000, 10_000, 0)
    per = t.client_times_s
    assert t.p50_client_time_s == float(np.percentile(per, 50))
    assert t.p90_client_time_s == float(np.percentile(per, 90))
    assert t.p50_client_time_s <= t.p90_client_time_s <= t.round_time_s


def test_round_timing_empty_cohort_quantiles():
    t = SimulatedNetwork(NetworkConfig(), 4).round([], 1000, 1000, 0)
    assert t.p50_client_time_s == 0.0 and t.p90_client_time_s == 0.0
    assert t.round_time_s == 0.0 and t.mean_client_time_s == 0.0


def test_transport_straggler_stretches_tail():
    base = NetworkConfig(straggler_prob=0.0, latency_jitter_ms=0.0, seed=3)
    strag = NetworkConfig(straggler_prob=1.0, straggler_slowdown=5.0,
                          latency_jitter_ms=0.0, seed=3)
    n0 = SimulatedNetwork(base, 8)
    n1 = SimulatedNetwork(strag, 8)
    t0 = n0.round(list(range(8)), 10_000, 10_000, 0)
    t1 = n1.round(list(range(8)), 10_000, 10_000, 0)
    assert t1.round_time_s == pytest.approx(5.0 * t0.round_time_s, rel=1e-6)


def test_per_round_draws_keyed_by_client_id_not_position():
    """Regression: latency/straggler draws used to come from one
    per-round Generator indexed by cohort POSITION, so permuting or
    resampling the cohort silently changed a client's timing. They are
    keyed by (seed, round, client_id) now: permuting the cohort permutes
    the times exactly, and a client keeps its draw across different
    cohorts in the same round."""
    cfg = NetworkConfig(straggler_prob=0.3, seed=11)
    net = SimulatedNetwork(cfg, 100)
    idx = np.array([7, 3, 50, 42, 99, 0])
    perm = np.array([3, 5, 0, 2, 4, 1])
    t1 = net.round(idx, 10_000, 10_000, round_idx=4)
    t2 = net.round(idx[perm], 10_000, 10_000, round_idx=4)
    assert np.array_equal(t1.client_times_s[perm], t2.client_times_s)
    assert t1.round_time_s == t2.round_time_s
    assert t1.slowest_client == t2.slowest_client
    # same client in a DIFFERENT cohort: identical time this round
    t3 = net.round(np.array([42, 1, 2]), 10_000, 10_000, round_idx=4)
    pos = int(np.where(idx == 42)[0][0])
    assert t3.client_times_s[0] == t1.client_times_s[pos]
    # ...and the stream still varies across rounds and seeds
    t4 = net.round(idx, 10_000, 10_000, round_idx=5)
    assert not np.array_equal(t1.client_times_s, t4.client_times_s)
    other = SimulatedNetwork(NetworkConfig(straggler_prob=0.3, seed=12), 100)
    t5 = other.round(idx, 10_000, 10_000, round_idx=4)
    assert not np.array_equal(t1.client_times_s, t5.client_times_s)


def test_event_clock_orders_and_breaks_ties_deterministically():
    from repro.comm.transport import EventClock
    clk = EventClock()
    clk.push(2.0, "late")
    clk.push(1.0, "early-first")
    clk.push(1.0, "early-second")
    assert len(clk) == 3 and clk.now == 0.0
    t, p = clk.pop()
    assert (t, p) == (1.0, "early-first")       # tie → insertion order
    assert clk.pop() == (1.0, "early-second")
    assert clk.now == 1.0
    assert clk.pop() == (2.0, "late")
    assert clk.now == 2.0 and len(clk) == 0


def test_commlog_bills_delivered_not_attempted():
    """Regression (billing bugfix): uplink_bytes must count only payloads
    the server actually received; the full cohort's sends stay visible as
    the _attempted diagnostic, and an explicit round_time_s override (the
    deadline-truncated effective wall clock) is what sums into
    sim_time_s."""
    net = SimulatedNetwork(NetworkConfig(seed=7), 8)
    log = CommLog()
    t = net.round([0, 1, 2, 3], 1000, 2000, 0)
    rec = log.record(t, round_time_s=0.5, delivered_uplink_bytes=3000)
    assert log.uplink_bytes == 3000
    assert log.uplink_bytes_attempted == 4000
    assert log.sim_time_s == 0.5
    assert rec["wire_up_bytes"] == 3000
    assert rec["wire_up_bytes_attempted"] == 4000
    assert rec["round_time_s"] == 0.5
    # defaults: everything delivered, raw round time
    rec2 = log.record(t)
    assert log.uplink_bytes == 3000 + 4000
    assert log.sim_time_s == 0.5 + t.round_time_s
    assert rec2["wire_up_bytes"] == rec2["wire_up_bytes_attempted"] == 4000


# -- FedSim wire mode --------------------------------------------------------


MC = MLPConfig(in_dim=16, hidden=32, depth=2, num_classes=4)
DATA = FederatedClassification(num_clients=12, num_classes=4, feature_dim=16,
                               alpha=0.5, seed=0)


def _run_sim(rounds=4, **fed_kw):
    fed_kw.setdefault("compressor", "topk")
    fed = FedConfig(algorithm="fedcams", eta=0.05, eta_l=0.1, local_steps=2,
                    num_clients=12, participating=4,
                    compress_ratio=1 / 8, **fed_kw)
    sim = FedSim(lambda p, b: mlp_loss(p, b, MC), fed)
    st = sim.init(pdefs.init_params(mlp_defs(MC), jax.random.PRNGKey(0)))
    rng = jax.random.PRNGKey(1)
    mets = []
    for r in range(rounds):
        rng, k1, k2 = jax.random.split(rng, 3)
        from repro.core.sampling import sample_clients
        idx = np.asarray(sample_clients(k1, 12, 4))
        b = DATA.round_batches(idx, r, 2, 16)
        st, met = sim.round(st, jax.tree.map(jnp.asarray, b),
                            jnp.asarray(idx), k2)
        mets.append(met)
    return st, mets, sim


def test_fedsim_wire_mode_matches_dense_path_bitwise():
    """encode->decode in the round changes nothing numerically (fp32)."""
    st0, _, _ = _run_sim(wire=False)
    st1, _, _ = _run_sim(wire=True)
    f0 = jax.flatten_util.ravel_pytree(st0.params)[0]
    f1 = jax.flatten_util.ravel_pytree(st1.params)[0]
    assert bool(jnp.all(f0 == f1))
    assert bool(jnp.all(st0.errors == st1.errors))


@pytest.mark.parametrize("comp", ["topk", "blocktopk", "sign"])
def test_fedsim_wire_metrics(comp):
    _, mets, sim = _run_sim(compressor=comp, wire=True)
    up = sim.codec.nbytes(sim._d)
    for i, m in enumerate(mets):
        assert m["wire_up_bytes"] == 4 * up
        assert m["round_time_s"] > 0
    # cumulative measured bytes and simulated wall-clock grow monotonically
    assert mets[-1]["wire_bytes"] == sum(m["wire_up_bytes"]
                                         + m["wire_down_bytes"] for m in mets)
    assert mets[-1]["sim_time_s"] == pytest.approx(
        sum(m["round_time_s"] for m in mets))
    # measured uplink agrees with the analytic accounting within the header
    analytic_bits = sim.comp.bits_per_message(sim._d)
    assert 8 * up <= analytic_bits + 8 * HEADER_BYTES + 7


def test_fedsim_wire_two_way_compresses_downlink():
    _, m_one, _ = _run_sim(wire=True)
    _, m_two, _ = _run_sim(wire=True, two_way=True)
    assert m_two[-1]["wire_down_bytes"] < m_one[-1]["wire_down_bytes"] / 3
    assert np.isfinite([float(m["loss"]) for m in m_two]).all()


def test_trainer_history_carries_wire_metrics():
    from repro.core.api import FederatedTrainer
    from repro.configs.base import TrainConfig
    tr = FederatedTrainer(
        fed=FedConfig(algorithm="fedcams", num_clients=8, participating=4,
                      local_steps=2, compressor="sign", eta=0.05, eta_l=0.1,
                      wire=True),
        train=TrainConfig(rounds=3, log_every=100),
        loss_fn=lambda p, b: mlp_loss(p, b, MC),
        init_params=pdefs.init_params(mlp_defs(MC), jax.random.PRNGKey(0)),
        network=SimulatedNetwork(NetworkConfig(seed=11), 8))
    tr.data = FederatedClassification(num_clients=8, num_classes=4,
                                      feature_dim=16, seed=0)
    hist = tr.run(log=None)
    for rec in hist:
        assert rec["wire_bytes"] > 0 and rec["round_time_s"] > 0
    assert hist[-1]["wire_bytes"] > hist[0]["wire_bytes"]


# -- mesh-path accounting ----------------------------------------------------


def test_mesh_wire_bytes_sparse_below_dense():
    tree = {"a": jnp.zeros((64, 64)), "b": jnp.zeros((300,))}
    dense = mesh_wire_bytes(FedConfig(algorithm="fedcams"), tree)
    assert dense == (64 * 64 + 300) * 4
    sparse = mesh_wire_bytes(
        FedConfig(algorithm="fedcams", aggregation="sparse",
                  compressor="blocktopk", compress_ratio=1 / 64), tree)
    packed = mesh_wire_bytes(
        FedConfig(algorithm="fedcams", aggregation="sparse",
                  compressor="packedsign"), tree)
    assert sparse < dense / 8
    assert packed < dense / 16
    # a tp-sharded client pushes every one of its tp device payloads
    assert mesh_wire_bytes(FedConfig(algorithm="fedcams"), tree,
                           tp=4) == 4 * dense
