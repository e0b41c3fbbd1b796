"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # see tests/hypothesis_fallback.py
    from hypothesis_fallback import given, settings, st

from repro.core.compressors import make_compressor, selection_to_dense
from repro.kernels import ref
from repro.kernels.fedams_update import fedams_update
from repro.kernels.ops import KernelImpl
from repro.kernels.sign_ef import sign_ef
from repro.kernels.topk_ef import tile_rows, topk_ef, topk_ef_sparse

settings.register_profile("ci", max_examples=15, deadline=None)
settings.load_profile("ci")


def _steps_n(block):
    """Elements of a top-k matrix of two full grid steps of
    :func:`tile_rows` blocks and a partial third (the row count differs
    per step, so the global ``idx`` offset is exercised)."""
    return (2 * tile_rows(block) + 3) * block


def _pair(seed, n):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=n), jnp.float32),
            jnp.asarray(r.normal(size=n) * 0.3, jnp.float32))


@pytest.mark.parametrize("n,block,k", [(256, 64, 4), (1024, 128, 16),
                                       (4096, 2048, 32), (8192, 1024, 1),
                                       (_steps_n(128), 128, 2)])
def test_topk_ef_matches_ref(n, block, k):
    x, e = _pair(0, n)
    h1, e1 = topk_ef(x, e, k=k, block=block)
    h2, e2 = ref.topk_ef_ref(x, e, k, block)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), atol=1e-6)


@given(st.integers(0, 10**6), st.sampled_from([64, 128, 256]),
       st.integers(1, 16))
def test_topk_ef_property(seed, block, k):
    x, e = _pair(seed, 4 * block)
    h1, e1 = topk_ef(x, e, k=k, block=block)
    h2, e2 = ref.topk_ef_ref(x, e, k, block)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-6)
    # EF identity holds inside the kernel
    np.testing.assert_allclose(np.asarray(h1 + e1), np.asarray(x + e),
                               atol=1e-5)


@pytest.mark.parametrize("n,block", [(256, 64), (2048, 2048), (8192, 1024)])
def test_sign_ef_matches_ref(n, block):
    x, e = _pair(1, n)
    h1, e1 = sign_ef(x, e, block=block)
    h2, e2 = ref.sign_ef_ref(x, e)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("option", [1, 2])
@pytest.mark.parametrize("n,block", [(512, 128), (4096, 4096)])
def test_fedams_update_matches_ref(option, n, block):
    r = np.random.default_rng(2)
    arrs = [jnp.asarray(np.abs(r.normal(size=n)) if i in (2, 3)
                        else r.normal(size=n), jnp.float32)
            for i in range(5)]
    kw = dict(eta=0.7, beta1=0.9, beta2=0.99, eps=1e-3, option=option)
    got = fedams_update(*arrs, block=block, **kw)
    want = ref.fedams_update_ref(*arrs, **kw)
    for g, w, nm in zip(got, want, "x m v vhat".split()):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-6, err_msg=nm)


@given(st.integers(0, 10**6), st.sampled_from([128, 256]),
       st.integers(1, 127))
def test_fedams_update_ragged_tail_property(seed, block, tail):
    """d % block != 0 goes through the pad-and-slice path: outputs keep the
    unpadded length and match the jitted jnp reference — m/v/v̂ bitwise
    (the zero pad lanes can't leak into real lanes); x gets a tiny
    tolerance: the multi-block interpret grid may compile the x division
    with a contracted FMA/rsqrt form (a few ulp of the increment), and
    tests/test_server_opt.py owns the single-block bitwise gate on x."""
    n = 2 * block + tail
    r = np.random.default_rng(seed)
    arrs = [jnp.asarray(np.abs(r.normal(size=n)) if i in (2, 3)
                        else r.normal(size=n), jnp.float32)
            for i in range(5)]
    for option in (1, 2):
        kw = dict(eta=0.7, beta1=0.9, beta2=0.99, eps=1e-3, option=option)
        got = fedams_update(*arrs, block=block, **kw)
        want = jax.jit(lambda *a: ref.fedams_update_ref(*a, **kw))(*arrs)
        for g, w, nm in zip(got, want, "x m v vhat".split()):
            assert g.shape == (n,), (nm, g.shape)
            if nm == "x":
                np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                           rtol=1e-6, atol=1e-6)
            else:
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                              err_msg=f"{nm} option={option}")


@given(st.integers(0, 10**6))
def test_fedams_kernel_vhat_monotone(seed):
    r = np.random.default_rng(seed)
    n = 256
    x = jnp.zeros(n)
    m = jnp.zeros(n)
    v = jnp.zeros(n)
    vh = jnp.zeros(n)
    for _ in range(4):
        d = jnp.asarray(r.normal(size=n) * 0.1, jnp.float32)
        x, m, v, vh2 = fedams_update(x, m, v, vh, d, eta=1.0, beta1=0.9,
                                     beta2=0.99, eps=1e-3, option=1, block=128)
        assert (np.asarray(vh2) >= np.asarray(vh) - 1e-12).all()
        assert (np.asarray(vh2) >= 1e-3 - 1e-12).all()
        vh = vh2


@pytest.mark.parametrize("n,block", [(4096, 2048), (_steps_n(128), 128)])
def test_topk_ef_keeps_exactly_k_on_ties(n, block):
    """Ties regression: a tile of equal |values| must keep EXACTLY k
    entries (lowest indices first, lax.top_k order) — the old threshold
    formulation (|x| >= kth) kept every tied entry, breaking the wire
    format's fixed (vals, idx) sizes and the bits_per_message accounting.
    Both kernels, over one grid step and over several with a partial
    last one."""
    k = 7
    x = jnp.ones(n, jnp.float32)
    e = jnp.zeros(n, jnp.float32)
    hat, ne = topk_ef(x, e, k=k, block=block)
    hat = np.asarray(hat).reshape(-1, block)
    for b in range(hat.shape[0]):
        kept = np.flatnonzero(hat[b])
        assert kept.tolist() == list(range(k)), (b, kept)
    vals, idx, _ = topk_ef_sparse(x, e, k=k, block=block)
    starts = np.arange(n // block)[:, None] * block
    assert np.array_equal(np.asarray(idx), starts + np.arange(k))
    assert (np.asarray(vals) == 1.0).all()
    # mixed signs and partial ties
    x2 = jnp.asarray(np.tile([2.0, -2.0, 1.0, -1.0], block // 4), jnp.float32)
    h2, _ = topk_ef(x2, jnp.zeros(block, jnp.float32), k=3, block=block)
    assert int((np.asarray(h2) != 0).sum()) == 3
    assert np.flatnonzero(np.asarray(h2)).tolist() == [0, 1, 4]
    # matches the dense blocktopk compressor bit for bit on ties
    comp = make_compressor("blocktopk", k / block, block)
    assert np.array_equal(np.asarray(comp.compress(x)), np.asarray(hat.reshape(-1)))
    sel = comp.select(x)
    assert np.array_equal(np.asarray(sel.idx), np.asarray(idx).reshape(-1))
    assert np.array_equal(np.asarray(sel.vals), np.asarray(vals).reshape(-1))


@pytest.mark.parametrize("n,block,k", [(256, 64, 4), (4096, 2048, 32),
                                       (8192, 1024, 1), (_steps_n(128), 128, 3),
                                       (_steps_n(256), 256, 4)])
def test_topk_ef_sparse_matches_dense_kernel(n, block, k):
    """The compacted (vals, idx) output scatters back to exactly the dense
    kernel's hat, the fused new_err is identical, idx are global, and both
    agree bit for bit with the jnp blocktopk compressor."""
    x, e = _pair(7, n)
    hat, ne = topk_ef(x, e, k=k, block=block)
    vals, idx, ne2 = topk_ef_sparse(x, e, k=k, block=block)
    assert vals.shape == idx.shape == (n // block, k)
    rec = jnp.zeros(n, jnp.float32).at[idx.reshape(-1)].set(vals.reshape(-1))
    assert np.array_equal(np.asarray(rec), np.asarray(hat))
    assert np.array_equal(np.asarray(ne2), np.asarray(ne))
    comp = make_compressor("blocktopk", k / block, block)
    sel = comp.select(x + e)
    assert np.array_equal(np.asarray(sel.idx), np.asarray(idx).reshape(-1))
    assert np.array_equal(np.asarray(sel.vals), np.asarray(vals).reshape(-1))
    assert np.array_equal(np.asarray(comp.compress(x + e)), np.asarray(hat))
    # per-block indices live in that block's global range
    lo = np.arange(n // block)[:, None] * block
    assert ((np.asarray(idx) >= lo) & (np.asarray(idx) < lo + block)).all()


@pytest.mark.parametrize("block,sizes", [(64, (64, 100, 300)),
                                         (128, (_steps_n(128) - 50,))])
def test_kernel_impl_topk_select_leaf_matches_compressor(block, sizes):
    """KernelImpl's fused selection agrees with the jnp compressor.select
    (vals/idx bit-identical incl. padded tails, over one grid step and
    several) and its new_err with the dense EF identity."""
    ki = KernelImpl(block=block)
    r = np.random.default_rng(9)
    for n in sizes:
        x = jnp.asarray(r.normal(size=n), jnp.float32)
        e = jnp.asarray(r.normal(size=n) * 0.2, jnp.float32)
        sel, ne = ki.topk_select_leaf(1 / 4, x, e)
        comp = make_compressor("blocktopk", 1 / 4, block)
        ref_sel = comp.select(x + e)
        assert np.array_equal(np.asarray(sel.idx), np.asarray(ref_sel.idx)), n
        assert np.array_equal(np.asarray(sel.vals), np.asarray(ref_sel.vals))
        hat = selection_to_dense(sel, n)
        np.testing.assert_array_equal(np.asarray(ne),
                                      np.asarray((x + e) - hat))


def test_kernel_impl_interpret_resolves_by_backend():
    """interpret=None lets the platform decide: the interpreter on the CPU
    platform only, compiled elsewhere; an explicit bool is honored."""
    ki = KernelImpl()
    assert ki.interpret is None
    expected = jax.default_backend() == "cpu"
    assert ki._interp is expected
    assert KernelImpl(interpret=True)._interp is True
    assert KernelImpl(interpret=False)._interp is False


def test_kernel_impl_padding_paths():
    """Non-multiple leaf sizes go through the zero-padding path exactly."""
    ki = KernelImpl(block=128)
    r = np.random.default_rng(3)
    for n in (100, 128, 300):
        x = jnp.asarray(r.normal(size=n), jnp.float32)
        e = jnp.asarray(r.normal(size=n) * 0.2, jnp.float32)
        h, ne = ki.ef_compress_leaf("sign", 1.0, x, e)
        h2, e2 = ref.sign_ef_ref(x, e)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h2), rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(ne), np.asarray(e2), rtol=1e-4,
                                   atol=1e-5)


def test_kernel_impl_tree_mask():
    ki = KernelImpl(block=64)
    comp = make_compressor("topk", 1 / 4)
    tree = {"a": jnp.ones((8, 8)), "b": jnp.arange(10.0)}
    err = jax.tree.map(jnp.zeros_like, tree)
    hat, ne = ki.ef_compress_tree(comp, tree, err, jnp.float32(0.0))
    assert all(float(jnp.abs(l).max()) == 0 for l in jax.tree.leaves(hat))
