"""One-pass fused server ingest (DESIGN.md §3).

Contract under test, per layer:

* ``server_ingest(impl="jnp")`` — the blocked-scatter fused path is
  BIT-IDENTICAL to the two-pass baseline (``server_aggregate_sparse`` +
  ``server_update``) at every ``server_state_dtype`` (float32, bfloat16,
  int8-blockscale: x, m, v, v̂ — including the int8 q codes and scales),
  across FedAMS options and fedamsgrad, over multiple chained steps (the
  storage round-trip accumulates identically).
* ``kernels.fedams_ingest`` — matches ``ref.fedams_ingest_ref`` (m/v/v̂
  bitwise); through ``server_ingest(impl="kernel")`` it stays within
  ≲1 ulp of the two-pass baseline (the kernel accumulates client
  collisions in a fori_loop, a different summation order on collided
  coordinates only).
* resolution — ``resolve_fused_ingest`` / FedSim eligibility: auto fuses
  the unchunked sparse blocktopk round (jnp on CPU), auto degrades to
  "off" when the γ diagnostic needs a dense aggregate, and a forced knob
  the build cannot honor raises instead of silently falling back.
* FedSim — multi-round trajectories are bit-identical fused vs two-pass
  at every state dtype (losses, params, EF errors); quantized second
  moments track the fp32 trajectory within a documented tolerance.
* pipeline shape — the fused jaxpr scatters only on the 2-D (nb, block)
  domain: no 1-D dense-length scatter-add (the materialized mean delta
  the two-pass hands between its jits) appears anywhere in the fused
  round.
"""
import dataclasses

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.configs.base import FedConfig
from repro.core.compressors import block_layout, make_compressor
from repro.core.rounds import FedSim
from repro.core.sampling import sample_clients
from repro.core.server_opt import (QuantState, init_server_state,
                                   server_ingest, server_update)
from repro.core.stages import resolve_fused_ingest, server_aggregate_sparse
from repro.data.synthetic import FederatedClassification
from repro.models import params as pdefs
from repro.models.convmixer import MLPConfig, mlp_defs, mlp_loss

# -- flat-leaf problem shared by the numerics tests --------------------------

D, BLOCK, NCLI, RATIO = 5000, 64, 7, 1 / 8
BS, NB = block_layout(D, BLOCK)


def _selections(seed, steps):
    """Per-step gathered (vals, idx) stacks from the real blocktopk
    selection (faithful layout: global idx in the zero-padded block
    domain, zero-valued pad entries in the tail block)."""
    comp = make_compressor("blocktopk", RATIO, BLOCK)
    r = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        tots = jnp.asarray(r.normal(size=(NCLI, D)), jnp.float32)
        sels = [comp.select(tots[j]) for j in range(NCLI)]
        out.append((jnp.stack([s.vals for s in sels]),
                    jnp.stack([s.idx for s in sels])))
    return out


def _fed(algo="fedcams", option=1, **kw):
    return FedConfig(algorithm=algo, compressor="blocktopk",
                     compress_ratio=RATIO, aggregation="sparse",
                     option=option, eta=0.5, track_gamma=False, **kw)


def _second_eq(a, b, msg):
    """Bitwise equality for one second-moment leaf in storage form."""
    if isinstance(a, QuantState):
        np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q),
                                      err_msg=f"{msg} q")
        np.testing.assert_array_equal(np.asarray(a.scale),
                                      np.asarray(b.scale),
                                      err_msg=f"{msg} scale")
    else:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=msg)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("algo,option", [("fedcams", 1), ("fedcams", 2),
                                         ("fedamsgrad", 1)])
def test_jnp_fused_bitwise_vs_two_pass(dtype, algo, option):
    """The fused jnp ingest IS the two-pass baseline, bit for bit, at
    every state dtype — chained over steps so the bf16/int8 storage
    round-trip (dequant → fp32 math → requant) is exercised on state the
    previous step wrote."""
    fed = _fed(algo, option, server_state_dtype=dtype)
    x_a = x_b = jnp.asarray(np.random.default_rng(1).normal(size=D),
                            jnp.float32)
    st_a = init_server_state(x_a, dtype, BS)
    st_b = init_server_state(x_b, dtype, BS)
    for step, (vals, idx) in enumerate(_selections(2, 3)):
        agg = server_aggregate_sparse(vals, idx, D, NCLI)
        x_a, st_a = server_update(fed, st_a, x_a, agg)
        x_b, st_b = server_ingest(fed, st_b, x_b, vals, idx, NCLI,
                                  block=BS, impl="jnp")
        msg = f"{algo} opt{option} {dtype} step{step}"
        np.testing.assert_array_equal(np.asarray(x_a), np.asarray(x_b),
                                      err_msg=f"{msg} x")
        np.testing.assert_array_equal(np.asarray(st_a.m), np.asarray(st_b.m),
                                      err_msg=f"{msg} m")
        _second_eq(st_a.v, st_b.v, f"{msg} v")
        _second_eq(st_a.vhat, st_b.vhat, f"{msg} vhat")
        assert int(st_a.t) == int(st_b.t)


@pytest.mark.parametrize("option", [1, 2])
def test_kernel_ingest_matches_ref(option):
    """Pallas ``fedams_ingest`` vs the per-client scatter-loop reference:
    m/v/v̂ bitwise; x gets the usual cross-program FMA/rsqrt allowance
    (tests/test_server_opt.py owns the single-program bitwise gate)."""
    from repro.kernels import ref
    from repro.kernels.fedams_ingest import fedams_ingest

    (vals, idx), = _selections(3, 1)
    r = np.random.default_rng(4)
    N = NB * BS
    k = vals.shape[1] // NB
    mk = lambda pos: jnp.asarray(
        np.abs(r.normal(size=N)) if pos else r.normal(size=N), jnp.float32)
    x, m, v, vh = mk(0), mk(0), mk(1), mk(1)
    kw = dict(n_div=NCLI, eta=0.5, beta1=0.9, beta2=0.99, eps=1e-3,
              option=option, block=BS)
    got = fedams_ingest(x, m, v, vh, vals.reshape(NCLI, NB, k),
                        idx.reshape(NCLI, NB, k), **kw)
    want = jax.jit(lambda *a: ref.fedams_ingest_ref(*a, **kw))(
        x, m, v, vh, vals.reshape(NCLI, NB, k), idx.reshape(NCLI, NB, k))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-6, atol=1e-6, err_msg="x")
    for g, w, nm in zip(got[1:], want[1:], "m v vhat".split()):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=nm)


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_kernel_ingest_near_two_pass(dtype):
    """``server_ingest(impl="kernel")`` vs the two-pass baseline: the
    kernel's per-client collision fori_loop reorders the scatter sum, so
    collided coordinates may move ≲1 ulp — everything else identical.
    int8: the q codes stay bitwise (requant quantizes away the ulp); the
    per-block scales carry the same ≲1-ulp allowance."""
    fed = _fed("fedcams", 1, server_state_dtype=dtype)
    x = jnp.asarray(np.random.default_rng(5).normal(size=D), jnp.float32)
    st_a = init_server_state(x, dtype, BS)
    st_b = init_server_state(x, dtype, BS)
    (vals, idx), = _selections(6, 1)
    agg = server_aggregate_sparse(vals, idx, D, NCLI)
    x_a, st_a = server_update(fed, st_a, x, agg)
    x_b, st_b = server_ingest(fed, st_b, x, vals, idx, NCLI,
                              block=BS, impl="kernel")
    np.testing.assert_allclose(np.asarray(x_a), np.asarray(x_b),
                               rtol=0, atol=1e-6, err_msg="x")
    np.testing.assert_allclose(np.asarray(st_a.m), np.asarray(st_b.m),
                               rtol=0, atol=1e-6, err_msg="m")
    if dtype == "int8":
        np.testing.assert_array_equal(np.asarray(st_a.v.q),
                                      np.asarray(st_b.v.q))
        np.testing.assert_allclose(np.asarray(st_a.v.scale),
                                   np.asarray(st_b.v.scale),
                                   rtol=1e-6, atol=0)
    else:
        np.testing.assert_allclose(np.asarray(st_a.v), np.asarray(st_b.v),
                                   rtol=0, atol=1e-6, err_msg="v")
        np.testing.assert_allclose(np.asarray(st_a.vhat),
                                   np.asarray(st_b.vhat),
                                   rtol=0, atol=1e-6, err_msg="vhat")


# -- resolution / validation -------------------------------------------------


MC = MLPConfig(in_dim=16, hidden=32, depth=2, num_classes=4)
DATA = FederatedClassification(num_clients=12, num_classes=4, feature_dim=16,
                               alpha=0.5, seed=0)
M, N, K = 12, 4, 2


def _make(**fed_kw):
    kw = dict(algorithm="fedcams", eta=0.05, eta_l=0.1, local_steps=K,
              num_clients=M, participating=N, compressor="blocktopk",
              compress_ratio=1 / 8, sparse_uplink=True, track_gamma=False)
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


def _run_loop(sim, st, batches, idx, keys, rounds):
    met = None
    for r in range(rounds):
        b_r = jax.tree.map(lambda x: x[r], batches)
        st, met = sim.round(st, b_r, idx[r], keys[r])
    return st, met


def test_fused_ingest_auto_resolution_and_validation():
    sim, _ = _make()                                 # eligible, CPU -> jnp
    assert sim._fused == "jnp"
    sim, _ = _make(track_gamma=True)                 # γ needs dense agg
    assert sim._fused == "off"
    sim, _ = _make(client_chunk=2)                   # chunked scan
    assert sim._fused == "off"
    sim, _ = _make(compressor="topk")                # ungrouped layout
    assert sim._fused == "off"
    sim, _ = _make(fused_ingest="off")               # explicit off
    assert sim._fused == "off"
    # forcing the knob on an ineligible round raises at build time
    with pytest.raises(ValueError, match="cannot fuse"):
        _make(fused_ingest="jnp", track_gamma=True)
    with pytest.raises(ValueError, match="cannot fuse"):
        _make(fused_ingest="kernel", client_chunk=2)
    # the resolver itself: forced kernel without a KernelImpl; auto picks
    # the kernel exactly where it compiles
    fed = _fed()
    with pytest.raises(ValueError, match="kernel_impl"):
        resolve_fused_ingest(dataclasses.replace(fed, fused_ingest="kernel"),
                             eligible=True, have_kernel=False, compiled=False)
    assert resolve_fused_ingest(fed, eligible=True, have_kernel=True,
                                compiled=True) == "kernel"
    assert resolve_fused_ingest(fed, eligible=True, have_kernel=True,
                                compiled=False) == "jnp"
    assert resolve_fused_ingest(fed, eligible=False, have_kernel=True,
                                compiled=True) == "off"


def test_state_dtype_config_validation():
    """Quantized second moments need an algorithm that overwrites v/v̂
    every round, and the int8 blockscale layout cannot shard."""
    with pytest.raises(ValueError, match="requant-drift"):
        FedConfig(algorithm="fedadam", server_state_dtype="bfloat16")
    with pytest.raises(ValueError, match="shard_server_state"):
        FedConfig(algorithm="fedcams", server_state_dtype="int8",
                  shard_server_state=True)
    for dtype in ("bfloat16", "int8"):               # fedams family is fine
        FedConfig(algorithm="fedcams", server_state_dtype=dtype)


# -- FedSim trajectories -----------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_sim_trajectory_bitwise_fused_vs_two_pass(dtype):
    """Multi-round FedSim: the fused round and the two-pass round produce
    the SAME trajectory bit for bit at every state dtype — losses, params,
    client EF errors."""
    R = 4
    batches, idx, keys = _stage(R)
    sim_f, st_f = _make(server_state_dtype=dtype)
    sim_o, st_o = _make(server_state_dtype=dtype, fused_ingest="off")
    assert sim_f._fused == "jnp" and sim_o._fused == "off"
    mets_f, mets_o = [], []
    for r in range(R):
        b_r = jax.tree.map(lambda x: x[r], batches)
        st_f, m_f = sim_f.round(st_f, b_r, idx[r], keys[r])
        st_o, m_o = sim_o.round(st_o, b_r, idx[r], keys[r])
        mets_f.append(m_f)
        mets_o.append(m_o)
    flat = lambda p: jax.flatten_util.ravel_pytree(p)[0]
    assert bool(jnp.all(flat(st_f.params) == flat(st_o.params))), dtype
    assert bool(jnp.all(st_f.errors == st_o.errors)), dtype
    assert st_f.bits == st_o.bits
    for m_f, m_o in zip(mets_f, mets_o):
        assert float(m_f["loss"]) == float(m_o["loss"]), dtype


def test_sim_quantized_state_tracks_f32_loss():
    """Documented tolerance for the quantized second-moment storage: the
    bf16/int8 trajectories track the fp32 one closely on this problem —
    the quantization error enters only via the stored v/v̂ read back next
    round (README perf table caveat)."""
    R = 4
    batches, idx, keys = _stage(R)
    _, met_f = _run_loop(*_make(server_state_dtype="float32"),
                         batches, idx, keys, R)
    for dtype in ("bfloat16", "int8"):
        _, met_q = _run_loop(*_make(server_state_dtype=dtype),
                             batches, idx, keys, R)
        lf, lq = float(met_f["loss"]), float(met_q["loss"])
        assert abs(lq - lf) <= 0.05 * max(1.0, abs(lf)), (dtype, lq, lf)


# -- pipeline shape: no dense mean delta on the fused path -------------------


def _scatter_add_shapes(jaxpr):
    """Output shapes of every scatter-add in a jaxpr, sub-jaxprs included."""
    shapes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter-add":
            shapes.extend(tuple(v.aval.shape) for v in eqn.outvars)
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for sub in vs:
                if isinstance(sub, ClosedJaxpr):
                    shapes.extend(_scatter_add_shapes(sub.jaxpr))
                elif isinstance(sub, Jaxpr):
                    shapes.extend(_scatter_add_shapes(sub))
    return shapes


def test_fused_jaxpr_has_no_dense_delta_scatter():
    """The structural claim behind the bytes-moved win: the fused ingest
    scatters client values straight onto the blocked (nb, block) domain —
    no 1-D dense-length scatter-add (the materialized mean delta) exists
    in its jaxpr. The two-pass aggregate is exactly that 1-D scatter."""
    fed = _fed()
    x = jnp.zeros(D, jnp.float32)
    st = init_server_state(x, "float32", BS)
    (vals, idx), = _selections(7, 1)
    fused = jax.make_jaxpr(
        lambda s, xx, vv, ii: server_ingest(fed, s, xx, vv, ii, NCLI,
                                            block=BS, impl="jnp"))(
        st, x, vals, idx)
    f_shapes = _scatter_add_shapes(fused.jaxpr)
    assert f_shapes and all(len(s) == 2 and s == (NB, BS)
                            for s in f_shapes), f_shapes
    two = jax.make_jaxpr(
        lambda vv, ii: server_aggregate_sparse(vv, ii, D, NCLI))(vals, idx)
    t_shapes = _scatter_add_shapes(two.jaxpr)
    assert any(len(s) == 1 and s[0] >= D for s in t_shapes), t_shapes


def test_fused_sim_round_has_no_dense_delta_scatter():
    """Same proof on the WHOLE fused FedSim round: every scatter-add that
    touches a dense-parameter-length 1-D buffer is gone (client-side EF
    scatters are batched 2-D and the server scatter is (nb, block)); the
    two-pass round keeps the 1-D aggregate scatter."""
    from repro.core.sim import _CoreState

    batches, idx, keys = _stage(1)
    b0 = jax.tree.map(lambda x: x[0], batches)

    def round_jaxpr(sim, st):
        return jax.make_jaxpr(
            lambda c, b, i, k: sim._round_impl(c, b, i, k, jnp.int32(0)))(
            _CoreState(*st[:5]), b0, idx[0], keys[0])

    sim_f, st_f = _make()
    d = sim_f._d
    dense_1d = [s for s in _scatter_add_shapes(round_jaxpr(sim_f, st_f).jaxpr)
                if len(s) == 1 and s[0] >= d]
    assert not dense_1d, dense_1d
    sim_o, st_o = _make(fused_ingest="off")
    dense_1d = [s for s in _scatter_add_shapes(round_jaxpr(sim_o, st_o).jaxpr)
                if len(s) == 1 and s[0] >= d]
    assert dense_1d, "two-pass round lost its aggregate scatter?"
