"""The pluggable local-update layer (core/local.py, DESIGN.md §8): rule
math, sgd inertness (explicit == default, both backends), convergence
sanity and EF exactness for every rule/knob, and the FedConfig
construction-time validation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.configs.base import FedConfig, ModelConfig, TrainConfig
from repro.core.local import (hetero_step_counts, local_lr, make_local_update,
                              run_local_steps)
from repro.core.sim import FedSim
from repro.core.sampling import sample_clients
from repro.data.synthetic import FederatedClassification
from repro.models import params as pdefs
from repro.models.convmixer import MLPConfig, mlp_defs, mlp_loss

MC = MLPConfig(in_dim=16, hidden=32, depth=2, num_classes=4)
DATA = FederatedClassification(num_clients=12, num_classes=4, feature_dim=16,
                               alpha=0.5, seed=0)
M, N, K = 12, 4, 2


def _run(rounds=20, seed=0, **fed_kw):
    kw = dict(algorithm="fedcams", eta=0.05, eta_l=0.1, local_steps=K,
              num_clients=M, participating=N, compressor="topk",
              compress_ratio=1 / 8)
    kw.update(fed_kw)
    fed = FedConfig(**kw)
    sim = FedSim(lambda p, b: mlp_loss(p, b, MC), fed)
    st = sim.init(pdefs.init_params(mlp_defs(MC), jax.random.PRNGKey(seed)))
    rng = jax.random.PRNGKey(seed + 1)
    losses = []
    for r in range(rounds):
        rng, k1, k2 = jax.random.split(rng, 3)
        idx = np.asarray(sample_clients(k1, M, N))
        b = DATA.round_batches(idx, r, K, 16)
        st, met = sim.round(st, jax.tree.map(jnp.asarray, b),
                            jnp.asarray(idx), k2)
        losses.append(float(met["loss"]))
    return losses, st


def _flat(params):
    return np.asarray(ravel_pytree(params)[0])


# -- rule math (unit level) --------------------------------------------------


def test_sgd_rule_math():
    rule = make_local_update(FedConfig(local_opt="sgd"))
    p = {"w": jnp.array([1.0, 2.0])}
    g = {"w": jnp.array([0.5, -1.0])}
    c = rule.init_carry(p)
    assert c == ()
    p1, c1 = rule.step(p, c, g, 0.1, p)
    np.testing.assert_array_equal(np.asarray(p1["w"]),
                                  np.array([0.95, 2.1], np.float32))


def test_sgdm_rule_math():
    """Heavy ball: u ← β·u + g, x ← x − η·u."""
    rule = make_local_update(FedConfig(local_opt="sgdm", local_momentum=0.5))
    p = {"w": jnp.array([1.0])}
    c = rule.init_carry(p)
    np.testing.assert_array_equal(np.asarray(c["w"]), np.array([0.0]))
    p1, u1 = rule.step(p, c, {"w": jnp.array([1.0])}, 0.1, p)
    assert float(p1["w"][0]) == pytest.approx(1.0 - 0.1 * 1.0)
    p2, u2 = rule.step(p1, u1, {"w": jnp.array([1.0])}, 0.1, p)
    # u2 = 0.5*1 + 1 = 1.5 -> p2 = 0.9 - 0.15
    assert float(u2["w"][0]) == pytest.approx(1.5)
    assert float(p2["w"][0]) == pytest.approx(0.9 - 0.15)


def test_prox_rule_math():
    """FedProx: x ← x − η·(g + μ·(x − x₀)) pulls toward the anchor."""
    rule = make_local_update(FedConfig(local_opt="prox", prox_mu=2.0))
    anchor = {"w": jnp.array([0.0])}
    p = {"w": jnp.array([1.0])}
    p1, _ = rule.step(p, rule.init_carry(p), {"w": jnp.array([0.0])}, 0.1,
                      anchor)
    # zero gradient: the proximal term alone moves x toward x0
    assert float(p1["w"][0]) == pytest.approx(1.0 - 0.1 * 2.0 * 1.0)


def test_local_lr_schedule():
    fed = FedConfig(eta_l=0.1, eta_l_decay=1.0)
    assert local_lr(fed, jnp.int32(7)) == 0.1  # plain float when off
    fed = FedConfig(eta_l=0.1, eta_l_decay=0.5)
    assert float(local_lr(fed, jnp.int32(0))) == pytest.approx(0.1)
    assert float(local_lr(fed, jnp.int32(3))) == pytest.approx(0.1 * 0.125)


def test_hetero_step_counts_range_and_determinism():
    fed = FedConfig(local_steps=4, local_steps_min=2)
    rng = jax.random.PRNGKey(0)
    k1 = np.asarray(hetero_step_counts(fed, rng, 64))
    k2 = np.asarray(hetero_step_counts(fed, rng, 64))
    np.testing.assert_array_equal(k1, k2)  # same rng -> same draw
    assert k1.min() >= 2 and k1.max() <= 4
    assert len(np.unique(k1)) > 1  # actually heterogeneous
    assert hetero_step_counts(FedConfig(local_steps=4), rng, 8) is None


def test_run_local_steps_masks_past_k_i():
    """k_i = 1 with K = 3 staged batches must equal a single step."""
    rule = make_local_update(FedConfig(local_opt="sgd"))
    p0 = {"w": jnp.array([1.0, -2.0])}
    batches = jnp.arange(6, dtype=jnp.float32).reshape(3, 2)

    def grad_fn(p, b):
        return jnp.sum(b), {"w": b}

    one, loss1 = run_local_steps(rule, grad_fn, p0, batches, 0.1,
                                 k_i=jnp.int32(1))
    ref, _ = rule.step(p0, (), {"w": batches[0]}, 0.1, p0)
    np.testing.assert_array_equal(np.asarray(one["w"]), np.asarray(ref["w"]))
    assert float(loss1) == pytest.approx(float(jnp.sum(batches[0])))


# -- sgd is inert (both backends) --------------------------------------------


def test_sgd_explicit_equals_default_sim():
    """local_opt="sgd" is the default rule: explicitly selecting it must be
    bit-identical (the new plumbing adds nothing to the sgd round)."""
    _, st_def = _run(rounds=4)
    _, st_sgd = _run(rounds=4, local_opt="sgd", local_momentum=0.37,
                     prox_mu=0.91)  # unused hyperparams must not leak
    np.testing.assert_array_equal(_flat(st_def.params), _flat(st_sgd.params))
    np.testing.assert_array_equal(np.asarray(st_def.errors),
                                  np.asarray(st_sgd.errors))


def _mesh_history(rounds=4, **fed_kw):
    from repro.core.api import FederatedTrainer
    from repro.data.synthetic import FederatedLMData
    from repro.launch.mesh import make_mesh
    from repro.models.model import Model

    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      dtype="float32")
    tr = FederatedTrainer(
        fed=FedConfig(algorithm="fedams", num_clients=1, local_steps=2,
                      client_axes=(), eta=0.3, eta_l=0.05, **fed_kw),
        train=TrainConfig(global_batch=4, seq_len=16, rounds=rounds,
                          remat_policy="none", log_every=100),
        model=Model(cfg, tp=1), mesh=make_mesh((1, 1), ("data", "model")))
    tr.lm_data = FederatedLMData(num_clients=1, vocab_size=64)
    h = tr.run(log=None)
    return [x["loss"] for x in h], _flat(tr.params)


def test_sgd_explicit_equals_default_mesh():
    h_def, p_def = _mesh_history()
    h_sgd, p_sgd = _mesh_history(local_opt="sgd", local_momentum=0.37,
                                 prox_mu=0.91)
    assert h_def == h_sgd
    np.testing.assert_array_equal(p_def, p_sgd)


# -- convergence sanity + the knob actually biting ---------------------------


@pytest.mark.parametrize("fed_kw", [{"local_opt": "sgdm"},
                                    {"local_opt": "sgdm",
                                     "local_momentum": 0.5},
                                    {"local_opt": "prox"},
                                    {"local_opt": "prox", "prox_mu": 0.1},
                                    {"eta_l_decay": 0.95},
                                    {"local_steps_min": 1},
                                    {"local_steps": 4,
                                     "local_steps_min": 2}])
def test_rule_converges_and_differs_from_sgd(fed_kw):
    losses, st = _run(**fed_kw)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05
    if fed_kw.get("local_steps", K) == K:
        _, st_sgd = _run(rounds=20)
        assert not np.array_equal(_flat(st.params), _flat(st_sgd.params)), \
            f"{fed_kw} produced the sgd round — knob is inert"


@pytest.mark.parametrize("fed_kw", [{"local_opt": "sgdm"},
                                    {"local_opt": "prox"},
                                    {"eta_l_decay": 0.9},
                                    {"local_steps_min": 1}])
def test_rule_converges_mesh(fed_kw):
    h, _ = _mesh_history(rounds=6, **fed_kw)
    assert np.isfinite(h).all()
    assert h[-1] < h[0]


# -- EF exactness per rule ---------------------------------------------------


@pytest.mark.parametrize("fed_kw", [{"local_opt": "sgd"},
                                    {"local_opt": "sgdm"},
                                    {"local_opt": "prox"},
                                    {"eta_l_decay": 0.9},
                                    {"local_steps_min": 1}])
def test_ef_tracks_exactly_what_was_sent(fed_kw):
    """For every local rule, the uplink's error feedback must satisfy
    new_err == (delta + err) − hat EXACTLY (same fp ops): EF tracks the
    value the wire carried, whatever produced the delta."""
    kw = dict(algorithm="fedcams", eta=0.05, eta_l=0.1, local_steps=K,
              num_clients=M, participating=N, compressor="topk",
              compress_ratio=1 / 8)
    kw.update(fed_kw)
    fed = FedConfig(**kw)
    sim = FedSim(lambda p, b: mlp_loss(p, b, MC), fed)
    st = sim.init(pdefs.init_params(mlp_defs(MC), jax.random.PRNGKey(0)))
    idx = np.arange(N)
    b = jax.tree.map(jnp.asarray, DATA.round_batches(idx, 0, K, 16))
    rng = jax.random.PRNGKey(3)
    start = sim.unravel(st.x_client)
    errs = jax.random.normal(jax.random.PRNGKey(9), (N, sim._d)) * 0.01
    k_all = hetero_step_counts(fed, rng, N)
    hats, new_errs, delta, _ = sim._clients_block(
        start, st.x_client, b, errs, jnp.arange(N), rng,
        local_lr(fed, jnp.int32(0)), k_all)
    np.testing.assert_array_equal(np.asarray(new_errs),
                                  np.asarray((delta + errs) - hats))
    assert np.abs(np.asarray(delta)).sum() > 0


# -- construction-time config validation -------------------------------------


@pytest.mark.parametrize("bad_kw", [{"algorithm": "fedcamsx"},
                                    {"algorithm": "FEDCAMS"},
                                    {"option": 3},
                                    {"compressor": "topkk"},
                                    {"aggregation": "sparse_topk"},
                                    {"local_opt": "adam"},
                                    {"mesh_sparse_impl": "triton"},
                                    {"eta_l_decay": 0.0},
                                    {"eta_l_decay": 1.5},
                                    {"local_steps_min": -1},
                                    {"local_steps": 2, "local_steps_min": 3}])
def test_fedconfig_rejects_typos_at_construction(bad_kw):
    with pytest.raises(ValueError, match="FedConfig"):
        FedConfig(**bad_kw)


def test_fedconfig_accepts_every_runtime_compressor_name():
    """The validated set must cover everything make_compressor accepts —
    including the "identity" alias for "none"."""
    for name in ("topk", "blocktopk", "sign", "packedsign", "randk", "int8",
                 "none", "identity"):
        FedConfig(compressor=name)


def test_fedconfig_replace_revalidates():
    fed = FedConfig()
    with pytest.raises(ValueError, match="FedConfig"):
        dataclasses.replace(fed, compressor="nope")
