"""The select-once sparse uplink fast path (DESIGN.md §3).

Contract under test, per layer:

* compressors — ``selection_to_dense(select(x), d) == compress(x)``
  bit-for-bit (same ``lax.top_k`` selection and tie-breaking), including
  the k=1 argmax fast path and padded final blocks.
* wire codecs — ``encode_from_selection(select(x)) == encode(x)`` byte for
  byte (the wire never re-runs top-k), ``decode_to_selection`` inverts it,
  and ``roundtrip_selection`` (the sim's shortcut past the byte shuffling)
  equals the full encode→decode roundtrip exactly for every value dtype.
* FedSim — with ``sparse_uplink`` on, a round's selection and
  error-feedback state are bit-identical to the dense reference path; the
  aggregate (and so params) differs only on coordinates several clients
  selected, by scatter-vs-reduce float reassociation (≲1 ulp/round),
  across ratios, client chunking, two-way, and wire on/off; the scan
  driver stays bit-identical to the per-round loop.
* pipeline shape — the wire-mode sparse round invokes ``lax.top_k`` at
  most once per client (zero times on the k=1 argmax path), verified by
  counting primitives in the traced jaxpr.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # see tests/hypothesis_fallback.py
    from hypothesis_fallback import given, settings, st

from repro.comm.wire import make_blocktopk_codec, make_topk_codec
from repro.configs.base import FedConfig
from repro.core.compressors import make_compressor, selection_to_dense
from repro.core.rounds import FedSim
from repro.core.sampling import sample_clients
from repro.core.stages import client_uplink, client_uplink_sparse
from repro.data.synthetic import FederatedClassification
from repro.models import params as pdefs
from repro.models.convmixer import MLPConfig, mlp_defs, mlp_loss

settings.register_profile("ci", max_examples=15, deadline=None)
settings.load_profile("ci")


def _vec(seed, d):
    return jnp.asarray(np.random.default_rng(seed).normal(size=d),
                       jnp.float32)


# -- compressor selection ----------------------------------------------------


@given(st.sampled_from(["topk", "blocktopk"]),
       st.sampled_from([1 / 2, 1 / 8, 1 / 64, 1 / 2048]),
       st.integers(8, 5000))
def test_selection_matches_dense_compress(name, ratio, d):
    """Property: the compacted selection scatters back to exactly the dense
    compressor output — same kept set, same values, any ratio/size
    (including padded final blocks and the k=1 argmax fast path)."""
    comp = make_compressor(name, ratio, 64)
    x = _vec(d, d)
    dense = np.asarray(comp.compress(x)).reshape(-1)
    sel = comp.select(x)
    rec = np.asarray(selection_to_dense(sel, d))
    assert np.array_equal(rec, dense), (name, ratio, d)


def test_selection_tie_breaking_matches_top_k():
    """Ties in |value| keep the lowest index, exactly like lax.top_k — on
    the top_k path and on the k=1 argmax fast path."""
    x = jnp.asarray([1.0, -2.0, 2.0, -2.0, 0.5, 2.0, -1.0, 0.0], jnp.float32)
    comp = make_compressor("topk", 2 / 8)
    sel = comp.select(x)
    assert sorted(np.asarray(sel.idx).tolist()) == [1, 2]
    one = make_compressor("topk", 1 / 8)          # k=1 -> argmax path
    sel1 = one.select(x)
    assert np.asarray(sel1.idx).tolist() == [1]
    assert float(sel1.vals[0]) == -2.0            # value, not |value|


def test_blocktopk_selection_padded_tail_block():
    """The final short block selects from the zero-padded domain: indices
    may point past d and carry exact 0.0 — dropped by the dense scatter."""
    d, block = 70, 64
    x = _vec(3, d)
    comp = make_compressor("blocktopk", 1 / 2, block)
    sel = comp.select(x)
    gidx = np.asarray(sel.idx)
    vals = np.asarray(sel.vals)
    assert (vals[gidx >= d] == 0.0).all()
    assert np.array_equal(np.asarray(selection_to_dense(sel, d)),
                          np.asarray(comp.compress(x)).reshape(-1))


# -- wire codec selection paths ---------------------------------------------


CODECS = {
    "topk_f32": lambda: make_topk_codec(1 / 8),
    "topk_f16": lambda: make_topk_codec(1 / 8, "float16"),
    "topk_bf16": lambda: make_topk_codec(1 / 8, "bfloat16"),
    "blocktopk_f32": lambda: make_blocktopk_codec(1 / 8, block=64),
    "blocktopk_int8": lambda: make_blocktopk_codec(1 / 8, block=64,
                                                   value_dtype="int8"),
    "blocktopk_k1": lambda: make_blocktopk_codec(1 / 64, block=64),
}


def _comp_of(name):
    kind = name.split("_")[0]
    ratio = 1 / 64 if name.endswith("k1") else 1 / 8
    return make_compressor(kind, ratio, 64)


@pytest.mark.parametrize("name", list(CODECS))
@pytest.mark.parametrize("d", [37, 100, 5000])
def test_encode_from_selection_byte_identical(name, d):
    """Packing the already-computed selection produces the exact bytes the
    dense encode produces — so wire mode can skip the second top-k."""
    codec, comp = CODECS[name](), _comp_of(name)
    x = _vec(d + 17, d)
    b_dense = codec.encode(x)
    b_sel = codec.encode_from_selection(comp.select(x), d)
    assert np.array_equal(np.asarray(b_dense), np.asarray(b_sel)), name


@pytest.mark.parametrize("name", list(CODECS))
@pytest.mark.parametrize("d", [37, 100, 5000])
def test_roundtrip_selection_equals_byte_roundtrip(name, d):
    """The sim's shortcut (roundtrip_selection) is bit-identical to the
    full encode->bytes->decode_to_selection trip, for every value dtype —
    this is what licenses skipping the byte shuffling inside the round."""
    codec, comp = CODECS[name](), _comp_of(name)
    x = _vec(d + 31, d)
    sel = comp.select(x)
    via_bytes = codec.decode_to_selection(
        codec.encode_from_selection(sel, d), d)
    direct = codec.roundtrip_selection(sel, d)
    assert np.array_equal(np.asarray(via_bytes.idx), np.asarray(direct.idx))
    assert np.array_equal(np.asarray(via_bytes.vals),
                          np.asarray(direct.vals)), name
    # and scattering the received selection equals the dense decode
    dec = np.asarray(codec.decode(codec.encode(x), d))
    rec = np.asarray(selection_to_dense(direct, d))
    assert np.array_equal(rec, dec), name


# -- FedSim: sparse vs dense reference path ----------------------------------


MC = MLPConfig(in_dim=16, hidden=32, depth=2, num_classes=4)
DATA = FederatedClassification(num_clients=12, num_classes=4, feature_dim=16,
                               alpha=0.5, seed=0)
M, N, K = 12, 4, 2


def _make(**fed_kw):
    kw = dict(algorithm="fedcams", eta=0.05, eta_l=0.1, local_steps=K,
              num_clients=M, participating=N, compressor="topk",
              compress_ratio=1 / 8)
    kw.update(fed_kw)
    fed = FedConfig(**kw)
    sim = FedSim(lambda p, b: mlp_loss(p, b, MC), fed)
    st = sim.init(pdefs.init_params(mlp_defs(MC), jax.random.PRNGKey(0)))
    return sim, st


def _stage(rounds):
    rng = jax.random.PRNGKey(1)
    idxs, keys, batches = [], [], []
    for r in range(rounds):
        rng, k1, k2 = jax.random.split(rng, 3)
        idx = np.asarray(sample_clients(k1, M, N))
        batches.append(DATA.round_batches(idx, r, K, 16))
        idxs.append(idx)
        keys.append(k2)
    stacked = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *batches)
    return stacked, jnp.asarray(np.stack(idxs)), jnp.stack(keys)


def _flat(params):
    return jax.flatten_util.ravel_pytree(params)[0]


def _run_loop(sim, st, batches, idx, keys, rounds):
    for r in range(rounds):
        b_r = jax.tree.map(lambda x: x[r], batches)
        st, met = sim.round(st, b_r, idx[r], keys[r])
    return st, met


PARITY_CASES = [
    {"compressor": "topk"},
    {"compressor": "topk", "wire": True},
    {"compressor": "topk", "wire": True, "two_way": True},
    {"compressor": "topk", "compress_ratio": 1 / 2048},
    {"compressor": "topk", "client_chunk": 2},
    {"compressor": "blocktopk"},
    {"compressor": "blocktopk", "wire": True},
    {"compressor": "blocktopk", "compress_ratio": 1 / 2048},
    {"compressor": "blocktopk", "client_chunk": 2, "wire": True},
]


@pytest.mark.parametrize("fed_kw", PARITY_CASES)
def test_sparse_round_bit_parity_with_dense(fed_kw):
    """One round from identical state: selection and EF errors are
    bit-identical to the dense path; params differ at most by the server
    update of the aggregate's scatter-vs-reduce reassociation (collided
    coordinates only, ≲1 ulp)."""
    batches, idx, keys = _stage(1)
    sim_d, st_d = _make(sparse_uplink=False, **fed_kw)
    sim_s, st_s = _make(sparse_uplink=True, **fed_kw)
    assert sim_s.sparse and not sim_d.sparse
    st_d, met_d = _run_loop(sim_d, st_d, batches, idx, keys, 1)
    st_s, met_s = _run_loop(sim_s, st_s, batches, idx, keys, 1)
    # client EF state: exactly equal, bit for bit
    assert bool(jnp.all(st_d.errors == st_s.errors)), fed_kw
    # server-side EF (two-way) is downstream of the aggregate, so it
    # inherits the aggregate's reassociation ulps
    np.testing.assert_allclose(np.asarray(st_s.server_error),
                               np.asarray(st_d.server_error),
                               rtol=0, atol=1e-8)
    # losses are computed before aggregation -> identical
    assert float(met_d["loss"]) == float(met_s["loss"])
    assert st_d.bits == st_s.bits
    # params: reassociation-only difference
    np.testing.assert_allclose(np.asarray(_flat(st_s.params)),
                               np.asarray(_flat(st_d.params)),
                               rtol=0, atol=1e-8)


@pytest.mark.parametrize("fed_kw", PARITY_CASES)
def test_sparse_trajectory_tracks_dense(fed_kw):
    """Multi-round: the 1-ulp/round aggregate reassociation stays a
    reassociation (no systematic drift) over several rounds."""
    R = 4
    batches, idx, keys = _stage(R)
    sim_d, st_d = _make(sparse_uplink=False, **fed_kw)
    sim_s, st_s = _make(sparse_uplink=True, **fed_kw)
    st_d, _ = _run_loop(sim_d, st_d, batches, idx, keys, R)
    st_s, _ = _run_loop(sim_s, st_s, batches, idx, keys, R)
    np.testing.assert_allclose(np.asarray(_flat(st_s.params)),
                               np.asarray(_flat(st_d.params)),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(st_s.errors),
                               np.asarray(st_d.errors), rtol=0, atol=1e-6)


@pytest.mark.parametrize("fed_kw", [
    {"compressor": "topk", "wire": True},
    {"compressor": "blocktopk", "wire": True, "two_way": True},
    {"compressor": "blocktopk", "compress_ratio": 1 / 2048,
     "client_chunk": 2},
    {"compressor": "topk", "local_opt": "sgdm", "eta_l_decay": 0.9,
     "local_steps_min": 1},
])
def test_sparse_scan_driver_bit_identical_to_loop(fed_kw):
    """run_rounds == R x round on the sparse path — same final state and
    per-round metrics, bit for bit (the sparse scatter lives inside the
    scanned body like every other stage)."""
    R = 4
    batches, idx, keys = _stage(R)
    sim_l, st_l = _make(sparse_uplink=True, **fed_kw)
    mets_l = []
    for r in range(R):
        b_r = jax.tree.map(lambda x: x[r], batches)
        st_l, met = sim_l.round(st_l, b_r, idx[r], keys[r])
        mets_l.append(met)
    sim_s, st_s = _make(sparse_uplink=True, **fed_kw)
    st_s, mets_s = sim_s.run_rounds(st_s, batches, idx, keys)
    assert bool(jnp.all(_flat(st_l.params) == _flat(st_s.params)))
    assert bool(jnp.all(st_l.errors == st_s.errors))
    assert st_l.bits == st_s.bits and st_l.round == st_s.round == R
    for m_l, m_s in zip(mets_l, mets_s):
        assert set(m_l) == set(m_s)
        for k in m_l:
            assert float(m_l[k]) == float(m_s[k]), (k, m_l[k], m_s[k])


def test_sparse_uplink_auto_resolution_and_validation():
    sim, _ = _make()                                   # auto: topk -> on
    assert sim.sparse
    sim, _ = _make(compressor="sign")                  # auto: sign -> off
    assert not sim.sparse
    sim, _ = _make(sparse_uplink=False)
    assert not sim.sparse
    with pytest.raises(ValueError, match="sparse_uplink"):
        FedConfig(algorithm="fedcams", compressor="sign", sparse_uplink=True)


# -- select-once: top_k count in the traced pipeline -------------------------


def _count_primitive(jaxpr, name: str) -> int:
    """Occurrences of a primitive in a jaxpr, including sub-jaxprs."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for sub in vs:
                if isinstance(sub, ClosedJaxpr):
                    n += _count_primitive(sub.jaxpr, name)
                elif isinstance(sub, Jaxpr):
                    n += _count_primitive(sub, name)
    return n


def _uplink_jaxpr(sparse: bool, ratio=1 / 8, d=256, c=3):
    from repro.comm.wire import make_wire_codec
    comp = make_compressor("blocktopk", ratio, 64)
    codec = make_wire_codec("blocktopk", ratio, 64)
    delta = jnp.zeros((c, d))
    errs = jnp.zeros((c, d))
    pos = jnp.arange(c)
    rng = jax.random.PRNGKey(0)
    if sparse:
        return jax.make_jaxpr(
            lambda tt, pp: client_uplink_sparse(comp, codec, d, rng, tt,
                                                pp))(delta + errs, pos)
    return jax.make_jaxpr(
        lambda dd, ee, pp: client_uplink(comp, codec, d, rng, dd, ee, pp))(
            delta, errs, pos)


def test_wire_mode_selects_once_per_client():
    """The sparse wire uplink traces exactly ONE lax.top_k (the
    compressor's selection); encode_from_selection adds none. At k=1 the
    argmax fast path brings it to zero. The dense wire uplink's top_k count
    is >= the sparse one (it re-selects inside codec.encode)."""
    sparse = _uplink_jaxpr(sparse=True)
    assert _count_primitive(sparse.jaxpr, "top_k") == 1
    dense = _uplink_jaxpr(sparse=False)
    assert _count_primitive(dense.jaxpr, "top_k") >= 1
    k1 = _uplink_jaxpr(sparse=True, ratio=1 / 64)     # kb=1 -> argmax
    assert _count_primitive(k1.jaxpr, "top_k") == 0
    assert _count_primitive(k1.jaxpr, "argmax") == 1


def test_full_wire_round_top_k_budget():
    """Whole sparse wire round: one selection per client block plus the
    round-level gamma diagnostic — nothing else runs top_k."""
    sim, st = _make(compressor="blocktopk", wire=True, sparse_uplink=True)
    batches, idx, keys = _stage(1)
    b0 = jax.tree.map(lambda x: x[0], batches)
    from repro.core.sim import _CoreState
    jaxpr = jax.make_jaxpr(
        lambda c, b, i, k: sim._round_impl(c, b, i, k, jnp.int32(0)))(
            _CoreState(*st[:5]), b0, idx[0], keys[0])
    assert _count_primitive(jaxpr.jaxpr, "top_k") == 2  # selection + gamma
