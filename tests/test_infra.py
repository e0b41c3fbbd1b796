"""Substrate tests: layers, checkpoint, data pipeline, HLO analyzer."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_pytree, save_pytree
from repro.data.synthetic import (FederatedClassification, FederatedLMData,
                                  dirichlet_label_partition)
from repro.launch.hlo_analysis import analyze
from repro.models import params as pdefs
from repro.models.layers import (embed_defs, embed_lookup, rms_norm, rope,
                                 sharded_xent, softcap)
from repro.sharding.rules import ParallelContext, attn_dims, pad_to

CTX = ParallelContext()


# -- layers ------------------------------------------------------------------


def test_rms_norm_unit_scale():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32)) * 7.0
    y = rms_norm(jnp.ones(32), x)
    rms = jnp.sqrt(jnp.mean(y * y, -1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, rtol=1e-3)


def test_rope_preserves_norm_and_relativity():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    pos = jnp.arange(8)[None]
    y = rope(x, pos)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(y, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)
    # shifting positions rotates q and k identically => q·k invariant
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 1, 16))
    def dots(off):
        qr = rope(q, jnp.asarray([[0 + off, 1 + off]]))
        kr = rope(k, jnp.asarray([[0 + off, 1 + off]]))
        return jnp.einsum("bshd,bshd->bs", qr, kr)
    np.testing.assert_allclose(np.asarray(dots(0)), np.asarray(dots(5)),
                               atol=1e-4)


def test_softcap_bounds():
    x = jnp.linspace(-1000, 1000, 101)
    y = softcap(x, 30.0)
    assert float(jnp.max(jnp.abs(y))) <= 30.0
    assert softcap(x, None) is x


def test_sharded_xent_matches_dense_tp1():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 33))
    labels = jax.random.randint(jax.random.PRNGKey(1), (4, 7), 0, 30)
    got = sharded_xent(logits, labels, CTX, true_vocab=30)
    lg = jnp.where(jnp.arange(33) < 30, logits, -jnp.inf)
    want = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(lg),
                                         labels[..., None], -1))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_embed_lookup_tp1():
    p = pdefs.init_params(embed_defs(16, 8), jax.random.PRNGKey(0))
    ids = jnp.asarray([[0, 5, 15]])
    out = embed_lookup(p, ids, CTX, "float32")
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(p["table"][jnp.asarray([0, 5, 15])]),
                               rtol=1e-6)


def test_attn_dims_padding():
    d = attn_dims(14, 2, 64, 16)     # internvl2 on tp16
    assert d.q_heads == 16 and d.q_local == 1 and not d.kv_sharded
    d = attn_dims(40, 40, 128, 16)   # qwen1.5-32b
    assert d.q_heads == 48 and d.kv_heads == 48
    d = attn_dims(32, 16, 128, 16)   # gemma2-27b
    assert d.q_heads == 32 and d.kv_sharded and d.kv_local == 1
    d = attn_dims(56, 8, 128, 16)    # deepseek-coder
    assert d.q_heads == 64 and not d.kv_sharded and d.group == 8
    assert pad_to(151655, 16) % 16 == 0


# -- checkpoint ---------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    save_pytree(str(tmp_path / "ck"), tree, {"round": 7})
    like = jax.tree.map(jnp.zeros_like, tree)
    restored, meta = load_pytree(str(tmp_path / "ck"), like)
    assert meta["round"] == 7
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.asarray(tree["a"]))


def test_checkpoint_structure_mismatch(tmp_path):
    save_pytree(str(tmp_path / "ck"), {"a": jnp.ones(3)})
    with pytest.raises(ValueError, match="mismatch"):
        load_pytree(str(tmp_path / "ck"), {"zz": jnp.ones(3)})


# -- data ----------------------------------------------------------------------


def test_dirichlet_partition_rows_sum_to_one():
    r = np.random.default_rng(0)
    p = dirichlet_label_partition(r, 10, 20, 0.3)
    np.testing.assert_allclose(p.sum(1), 1.0, rtol=1e-6)
    iid = dirichlet_label_partition(r, 10, 5, np.inf)
    np.testing.assert_allclose(iid, 0.1)


def test_classification_noniid_skew():
    d_noniid = FederatedClassification(num_clients=8, alpha=0.05, seed=1)
    d_iid = FederatedClassification(num_clients=8, alpha=np.inf, seed=1)
    ent = lambda p: -(p * np.log(p + 1e-12)).sum(1).mean()
    assert ent(d_noniid.label_dist) < ent(d_iid.label_dist) - 0.5


def test_classification_batches_deterministic():
    d = FederatedClassification(num_clients=4, seed=3)
    b1 = d.client_batch(1, 5, 8)
    b2 = d.client_batch(1, 5, 8)
    np.testing.assert_allclose(b1["x"], b2["x"])
    assert (b1["y"] == b2["y"]).all()
    b3 = d.client_batch(1, 6, 8)
    assert not np.allclose(b1["x"], b3["x"])


def test_lm_data_shapes_and_planted_structure():
    d = FederatedLMData(num_clients=4, vocab_size=64, seed=0)
    b = d.client_batch(0, 0, 16, 32)
    assert b["tokens"].shape == (16, 32)
    follow = (b["tokens"] * d.mult + d.add) % 64
    frac = (b["labels"] == follow).mean()
    assert 0.3 < frac < 0.8  # coin prob 0.5 + accidental matches


def test_lm_mesh_batch_layout():
    d = FederatedLMData(num_clients=4, vocab_size=64, seed=0)
    mb = d.mesh_batch(0, 3, 8, 16)
    assert mb["tokens"].shape == (3, 8, 16)


# -- HLO analyzer ---------------------------------------------------------------


def test_hlo_analyzer_scan_trip_count():
    from jax import lax

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return lax.scan(body, x, None, length=7)[0]

    x = jnp.zeros((64, 64))
    c = jax.jit(f).lower(x, x).compile()
    hc = analyze(c.as_text())
    assert hc.flops == 2 * 64 ** 3 * 7


def test_hlo_analyzer_nested_scans():
    from jax import lax

    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            return lax.scan(inner, c, None, length=3)[0], None
        return lax.scan(outer, x, None, length=5)[0]

    x = jnp.zeros((32, 32))
    c = jax.jit(f).lower(x, x).compile()
    hc = analyze(c.as_text())
    assert hc.flops == 2 * 32 ** 3 * 15


def test_backend_spec_keyed_by_device_kind():
    """Peaks are looked up by ``device_kind``; a device with no published
    peaks (the CPU here) raises instead of borrowing another chip's."""
    from repro.launch.mesh import backend_spec
    v5e = backend_spec("TPU v5 lite")
    assert (v5e.peak_flops_bf16, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        backend_spec("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        backend_spec()      # the first local device: the CPU


def test_compile_cache_dir_is_fixed(monkeypatch):
    """Without JAX_COMPILATION_CACHE_DIR the cache sits at the checkout's
    .jax_cache; with it set, JAX's own reading of it is left alone."""
    from repro.launch import cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cache.enable_compile_cache()
        assert path == os.path.join(cache.CHECKOUT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
