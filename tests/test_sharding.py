"""Distribution correctness, run in subprocesses with fake host devices (so
the rest of the suite keeps seeing one device).

The key check: the shard_map mesh execution of the federated round is
numerically equivalent to the pure-simulation path (same clients, same
batches, same server math) — the SPMD mapping introduces no drift."""
import json

import pytest

from conftest import run_forced_devices

def run_sub(code: str, devices: int = 8) -> str:
    return run_forced_devices(code, devices, timeout=600)


@pytest.mark.slow
def test_mesh_round_matches_simulation():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import ModelConfig, FedConfig, TrainConfig
        from repro.core.rounds import (FedSim, build_fed_round,
                                       init_fed_state, fed_state_defs,
                                       fed_batch_defs)
        from repro.models.model import Model
        from repro.models import params as pdefs
        from repro.sharding.rules import ParallelContext
        from repro.launch.mesh import make_mesh

        cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                          num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                          dtype="float32")
        m, K, GB, S = 4, 2, 8, 16
        fed = FedConfig(algorithm="fedams", num_clients=m, local_steps=K,
                        compressor="none", client_axes=("data",),
                        eta=0.3, eta_l=0.05)
        train = TrainConfig(global_batch=GB, seq_len=S, remat_policy="none")
        mesh = make_mesh((4, 2), ("data", "model"))
        model = Model(cfg, tp=2)
        ctx = ParallelContext(model_axis="model", tp=2,
                              client_axes=("data",), num_clients=m)
        sdefs = fed_state_defs(model, fed)
        ssp = jax.tree.map(lambda d: d.spec, sdefs, is_leaf=pdefs.is_def)
        bsp = jax.tree.map(lambda d: d.spec, fed_batch_defs(model, fed, train),
                           is_leaf=pdefs.is_def)
        rnd = jax.jit(jax.shard_map(build_fed_round(model, fed, train, ctx),
                      mesh=mesh, in_specs=(ssp, bsp, P()),
                      out_specs=(ssp, {"loss": P(), "wire_up_bytes": P()})))
        state = init_fed_state(model, fed, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 64, size=(K, GB, S)).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks),
                 "labels": jnp.asarray(np.roll(toks, -1, -1))}
        mesh_losses = []
        for r in range(3):
            state, met = rnd(state, batch, jnp.int32(r))
            mesh_losses.append(float(met["loss"]))

        # --- pure simulation on the same data/clients -------------------
        model1 = Model(cfg, tp=1)
        ctx1 = ParallelContext()
        sim = FedSim(lambda p, b: model1.loss(p, b, ctx1,
                                              remat_policy="none"), fed)
        params = init_fed_state(model1, fed, jax.random.PRNGKey(0)).params
        st = sim.init(params)
        per = GB // m
        cb = {"tokens": jnp.asarray(toks.reshape(K, m, per, S)
                                    .transpose(1, 0, 2, 3)),
              "labels": jnp.asarray(np.roll(toks, -1, -1)
                                    .reshape(K, m, per, S)
                                    .transpose(1, 0, 2, 3))}
        sim_losses = []
        for r in range(3):
            st, met = sim.round(st, cb, jnp.arange(m), jax.random.PRNGKey(r))
            sim_losses.append(float(met["loss"]))
        print(json.dumps({"mesh": mesh_losses, "sim": sim_losses}))
    """)
    data = json.loads(out.strip().splitlines()[-1])
    for a, b in zip(data["mesh"], data["sim"]):
        assert abs(a - b) < 5e-3, data


@pytest.mark.slow
def test_sparse_aggregation_equals_dense_topk():
    """Beyond-paper sparse all_gather aggregation == dense psum of the same
    per-leaf top-k compression (bitwise semantics, modulo float order)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import ModelConfig, FedConfig, TrainConfig
        from repro.core.rounds import (build_fed_round, init_fed_state,
                                       fed_state_defs, fed_batch_defs)
        from repro.models.model import Model
        from repro.models import params as pdefs
        from repro.sharding.rules import ParallelContext
        from repro.launch.mesh import make_mesh

        cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                          num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                          dtype="float32")
        mesh = make_mesh((4, 2), ("data", "model"))
        model = Model(cfg, tp=2)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 64, size=(2, 8, 16)).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks),
                 "labels": jnp.asarray(np.roll(toks, -1, -1))}
        res = {}
        for agg in ("dense", "sparse"):
            fed = FedConfig(algorithm="fedcams", num_clients=4, local_steps=2,
                            compressor="topk", compress_ratio=1/4,
                            aggregation=agg, client_axes=("data",),
                            eta=0.3, eta_l=0.05)
            train = TrainConfig(global_batch=8, seq_len=16,
                                remat_policy="none")
            ctx = ParallelContext(model_axis="model", tp=2,
                                  client_axes=("data",), num_clients=4)
            sdefs = fed_state_defs(model, fed)
            ssp = jax.tree.map(lambda d: d.spec, sdefs, is_leaf=pdefs.is_def)
            bsp = jax.tree.map(lambda d: d.spec,
                               fed_batch_defs(model, fed, train),
                               is_leaf=pdefs.is_def)
            rnd = jax.jit(jax.shard_map(
                build_fed_round(model, fed, train, ctx), mesh=mesh,
                in_specs=(ssp, bsp, P()), out_specs=(ssp, {"loss": P(), "wire_up_bytes": P()}),
                check_vma=True))
            state = init_fed_state(model, fed, jax.random.PRNGKey(0))
            losses = []
            for r in range(3):
                state, met = rnd(state, batch, jnp.int32(r))
                losses.append(float(met["loss"]))
            res[agg] = losses
        print(json.dumps(res))
    """)
    data = json.loads(out.strip().splitlines()[-1])
    for a, b in zip(data["dense"], data["sparse"]):
        assert abs(a - b) < 5e-3, data


@pytest.mark.slow
def test_multipod_mesh_and_hierarchical_client():
    """3-axis (pod, data, model) mesh lowers and runs: per_data clients over
    (pod,data) and hierarchical per_pod clients with within-client DP."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import ModelConfig, FedConfig, TrainConfig
        from repro.core.rounds import (build_fed_round, init_fed_state,
                                       fed_state_defs, fed_batch_defs)
        from repro.models.model import Model
        from repro.models import params as pdefs
        from repro.sharding.rules import ParallelContext
        from repro.launch.mesh import make_mesh

        cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                          num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                          dtype="float32")
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        model = Model(cfg, tp=2)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 64, size=(1, 8, 16)).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks),
                 "labels": jnp.asarray(np.roll(toks, -1, -1))}
        out = {}
        for mode, axes, m in (("per_data", ("pod", "data"), 4),
                              ("per_pod", ("pod",), 2)):
            fed = FedConfig(algorithm="fedcams", num_clients=m, local_steps=1,
                            compressor="topk", compress_ratio=1/4,
                            client_axes=axes, eta=0.3, eta_l=0.05)
            train = TrainConfig(global_batch=8, seq_len=16,
                                remat_policy="none")
            hier = "data" not in axes
            ctx = ParallelContext(model_axis="model", tp=2,
                                  data_axis="data" if hier else None,
                                  dp=2 if hier else 1,
                                  client_axes=axes, num_clients=m)
            sdefs = fed_state_defs(model, fed)
            ssp = jax.tree.map(lambda d: d.spec, sdefs, is_leaf=pdefs.is_def)
            bsp = jax.tree.map(lambda d: d.spec,
                               fed_batch_defs(model, fed, train),
                               is_leaf=pdefs.is_def)
            rnd = jax.jit(jax.shard_map(
                build_fed_round(model, fed, train, ctx), mesh=mesh,
                in_specs=(ssp, bsp, P()), out_specs=(ssp, {"loss": P(), "wire_up_bytes": P()}),
                check_vma=True))
            state = init_fed_state(model, fed, jax.random.PRNGKey(0))
            state, met = rnd(state, batch, jnp.int32(0))
            out[mode] = float(met["loss"])
        print(json.dumps(out))
    """)
    data = json.loads(out.strip().splitlines()[-1])
    assert all(v == v for v in data.values())  # finite


@pytest.mark.slow
def test_seq_sharded_decode_matches_unsharded():
    """long_500k mechanism: LSE-combined attention over a sequence-sharded
    cache equals the single-device decode."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import ModelConfig
        from repro.models.model import Model
        from repro.models import params as pdefs
        from repro.sharding.rules import ParallelContext
        from repro.launch.mesh import make_mesh

        cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                          num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                          dtype="float32")
        model = Model(cfg, tp=1)
        params = model.init(jax.random.PRNGKey(0))
        B, max_len, S = 1, 16, 10
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 64)

        # reference: unsharded decode
        ctx0 = ParallelContext()
        caches = model.init_cache(B, max_len)
        ref = []
        for i in range(S):
            lg, caches = model.decode_step(params, toks[:, i:i+1], caches,
                                           jnp.int32(i), ctx0,
                                           max_len=max_len)
            ref.append(np.asarray(lg))

        # seq-sharded over 4 "data" devices
        mesh = make_mesh((4,), ("data",))
        ctx = ParallelContext(seq_axis="data", seq_shards=4)
        from repro.launch.steps import remap_defs
        cdefs = remap_defs(model.cache_defs(B, max_len, seq_sharded=True),
                           {"model": None})
        csp = jax.tree.map(lambda d: d.spec, cdefs, is_leaf=pdefs.is_def)
        psp = jax.tree.map(lambda d: P(*[None]*len(d.shape)), model.defs(),
                           is_leaf=pdefs.is_def)
        step = jax.jit(jax.shard_map(
            lambda p, t, c, pos: model.decode_step(p, t, c, pos, ctx,
                                                   max_len=max_len),
            mesh=mesh, in_specs=(psp, P(), csp, P()),
            out_specs=(P(), csp)))
        from repro.models.stack import init_cache_value
        caches = init_cache_value(cdefs)
        errs = []
        for i in range(S):
            lg, caches = step(params, toks[:, i:i+1], caches, jnp.int32(i))
            errs.append(float(np.abs(np.asarray(lg) - ref[i]).max()))
        print(json.dumps({"max_err": max(errs)}))
    """)
    data = json.loads(out.strip().splitlines()[-1])
    assert data["max_err"] < 1e-3, data


@pytest.mark.slow
def test_tp_serving_prefill_decode():
    """Tensor-parallel serving: prefill+decode under shard_map TP2 matches
    the single-device path (incl. vocab-sharded greedy sampling)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import ModelConfig
        from repro.models.model import Model, greedy_sample
        from repro.models import params as pdefs
        from repro.sharding.rules import ParallelContext
        from repro.launch.mesh import make_mesh

        cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                          num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                          dtype="float32")
        max_len = 16
        model = Model(cfg, tp=2)
        params = model.init(jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)

        # reference single-device generation
        ctx0 = ParallelContext()
        lg, caches = model.prefill(params, prompts, ctx0, max_len=max_len)
        tok = greedy_sample(lg, ctx0)[:, None].astype(jnp.int32)
        ref = [np.asarray(tok[:, 0])]
        for i in range(5):
            lg, caches = model.decode_step(params, tok, caches,
                                           jnp.int32(8 + i), ctx0,
                                           max_len=max_len)
            tok = greedy_sample(lg, ctx0)[:, None].astype(jnp.int32)
            ref.append(np.asarray(tok[:, 0]))

        mesh = make_mesh((2,), ("model",))
        ctx = ParallelContext(model_axis="model", tp=2)
        psp = jax.tree.map(lambda d: d.spec, model.defs(),
                           is_leaf=pdefs.is_def)
        cdefs = model.cache_defs(2, max_len, seq_sharded=False)
        cdefs = jax.tree.map(
            lambda d: d, cdefs, is_leaf=pdefs.is_def)
        from repro.launch.steps import remap_defs
        cdefs = remap_defs(cdefs, {"data": None})
        csp = jax.tree.map(lambda d: d.spec, cdefs, is_leaf=pdefs.is_def)
        prefill = jax.jit(jax.shard_map(
            lambda p, t: model.prefill(p, t, ctx, max_len=max_len),
            mesh=mesh, in_specs=(psp, P()),
            out_specs=(P(None, "model"), csp)))
        def dstep(p, t, c, pos):
            lg, c2 = model.decode_step(p, t, c, pos, ctx, max_len=max_len)
            return greedy_sample(lg, ctx), c2
        decode = jax.jit(jax.shard_map(
            dstep, mesh=mesh, in_specs=(psp, P(), csp, P()),
            out_specs=(P(), csp)))

        lg, caches = prefill(params, prompts)
        tok = greedy_sample(lg, ParallelContext())[:, None].astype(jnp.int32)
        got = [np.asarray(tok[:, 0])]
        for i in range(5):
            t2, caches = decode(params, tok, caches, jnp.int32(8 + i))
            tok = t2[:, None].astype(jnp.int32)
            got.append(np.asarray(tok[:, 0]))
        print(json.dumps({"ref": np.stack(ref, 1).tolist(),
                          "got": np.stack(got, 1).tolist()}))
    """)
    data = json.loads(out.strip().splitlines()[-1])
    assert data["ref"] == data["got"], data
