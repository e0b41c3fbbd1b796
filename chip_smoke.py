"""Chip smoke: the federated FedCAMS round at full xLSTM-350M width on TPU.

    python chip_smoke.py [--seed N] [--rounds R]   # one chip
    python chip_smoke.py --four-chip               # one host, four chips

One process drives the chip(s) through the program's own entry points
(``repro.launch.train.main``, ``repro.core.api.FederatedTrainer``) with
random weights and synthetic client data made from ``--seed``. Every phase runs and reports each check; a failed
check or a phase that raises makes the script exit non-zero at the end,
and the last line of standard output is the JSON result only when every
check of every phase passed. Without a TPU it exits non-zero before any
phase.

One chip (xlstm-350m: 24 layers, d_model 1024, vocab 50304; fedcams,
blocktopk at 1/64, sparse aggregation, K=2 local steps):

* A — the main path: compiled selection kernel and fused ingest kernel,
  ``--rounds`` rounds; losses finite, params moved; compile time, round
  seconds and peak HBM printed.
* B — kernel contracts on chip: ``topk_ef_sparse``/``topk_ef`` equal
  ``Compressor.select``/``compress`` bit for bit at the largest leaf and
  at one layer leaf; ``fedams_ingest`` equals its oracle bit for bit and
  the two-pass baseline on every coordinate at most one client selected,
  at (4 clients, k=32) and (10 clients, k=1); ``fedams_update`` equals
  ``server_update`` bit for bit.
* C — the other entry point: ``FederatedTrainer`` and ``launch.train``
  (jnp providers) leave the same state bit for bit after one round.
* D — kernel providers against jnp providers, every round from the same
  state and batch, in one program that shares the local phase: the
  whole federated state agrees bit for bit (depth cut to 4 layers so
  three states fit the chip). Two separately compiled round programs do
  not share it: XLA compiles the bf16 local steps differently in each,
  which C's log line shows on the round-0 loss.

Four chips (``--four-chip``): xlstm-350m with 4 clients, one per chip; the
compacted-Selection all_gather against the dense psum from the same init.
Their losses agree, and every chip's peak stays within its share of the
state's layout plus the round's own working set.

Times are first observations on whatever chip ran the script, not claims.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

GB = 1e9


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    """The devices, or exit non-zero: a CPU run here would measure nothing
    this script is for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing was run",
              file=sys.stderr)
        sys.exit(2)
    return devices


def peaks(devices):
    return [d.memory_stats()["peak_bytes_in_use"] / GB for d in devices]


FAILED = []


def check(ok: bool, what: str) -> None:
    log(f"[check] {what}: {'ok' if ok else 'FAILED'}")
    if not ok:
        FAILED.append(what)


def run_phase(name, fn, *args):
    """Run one phase; a phase that raises is a failure of the run, and
    the remaining phases still run and report."""
    t0 = time.perf_counter()
    try:
        result = fn(*args)
    except Exception:
        traceback.print_exc()
        sys.stdout.flush()
        FAILED.append(f"phase {name} raised")
        result = None
    log(f"[{name}] phase took {time.perf_counter() - t0:.0f}s")
    return result


def train(argv):
    from repro.launch.train import main
    return main(argv)


def base_args(seed: int, rounds: int, dp: int = 1):
    return ["--arch", "xlstm-350m", "--rounds", str(rounds),
            "--seed", str(seed), "--dp", str(dp),
            "--algorithm", "fedcams", "--compressor", "blocktopk",
            "--ratio", str(1 / 64), "--local-steps", "2",
            "--global-batch", str(8 * dp), "--seq-len", "64"]


def report(tag, out):
    hist = out["history"]
    log(f"[{tag}] kernels={out['kernels']} compile_s={out['compile_s']:.1f} "
        f"losses={[h['loss'] for h in hist]} "
        f"round_s={[round(h['seconds'], 4) for h in hist]} "
        f"(first observation, not a claim)")


def to_host(state):
    import jax
    import numpy as np
    return {f: [np.asarray(x) for x in jax.tree.leaves(getattr(state, f))]
            for f in state._fields}


def state_diff(a, b):
    """Per field: (differing elements, max |a - b|)."""
    import numpy as np
    out = {}
    for f in a:
        n, mx = 0, 0.0
        for x, y in zip(a[f], b[f]):
            ne = x != y
            n += int(ne.sum())
            if ne.any():
                mx = max(mx, float(np.abs(x.astype(np.float64)
                                          - y.astype(np.float64))[ne].max()))
        out[f] = (n, mx)
    return out


def finite(hist):
    import math
    return all(math.isfinite(h["loss"]) for h in hist)


# -- one chip -----------------------------------------------------------------


def phase_main_path(seed: int, rounds: int, devices):
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_arch
    from repro.models import params as pdefs
    from repro.models.model import Model

    out = train(base_args(seed, rounds) + ["--aggregation", "sparse"])
    report("A", out)
    check(out["kernels"] == "compiled",
          "A: the entry point runs the compiled kernels on TPU")
    check(finite(out["history"]), "A: every round's loss is finite")
    init = pdefs.init_params(Model(get_arch("xlstm-350m").model).defs(),
                             jax.random.PRNGKey(seed))
    moved = float(jnp.sqrt(sum(
        jnp.sum(jnp.square(a - b)) for a, b in
        zip(jax.tree.leaves(out["state"].params), jax.tree.leaves(init)))))
    del init
    log(f"[A] |params - init| = {moved}")
    check(moved > 0, "A: the params moved")
    log(f"[A] peak HBM GB per device {peaks(devices)}")
    return out["history"]


def _rand(key, n, scale=1.0):
    import jax
    return jax.random.normal(key, (n,), "float32") * scale


def _bitwise(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and np.array_equal(
        a.view(np.uint32) if a.dtype.itemsize == 4 else a,
        b.view(np.uint32) if b.dtype.itemsize == 4 else b)


def phase_kernels(seed: int, leaf_sizes):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import FedConfig
    from repro.core.compressors import block_layout, make_compressor
    from repro.core.server_opt import ServerState, server_update
    from repro.core.stages import server_aggregate_sparse
    from repro.kernels import ref
    from repro.kernels.fedams_ingest import fedams_ingest
    from repro.kernels.fedams_update import fedams_update
    from repro.kernels.topk_ef import topk_ef, topk_ef_sparse

    key = jax.random.PRNGKey(seed + 1)
    ratio = 1 / 64
    for n in leaf_sizes:
        bs, nb = block_layout(n, 2048)
        k = max(1, round(ratio * bs))
        k1, k2, key = jax.random.split(key, 3)
        x, e = _rand(k1, nb * bs), _rand(k2, nb * bs, 0.3)
        # exact ties in |value| across every block: the lowest-index rule
        x = x.at[::97].set(0.5).at[1::97].set(-0.5)
        comp = make_compressor("blocktopk", ratio, bs)
        tot = x + e
        t0 = time.perf_counter()
        vals, idx, ne = jax.block_until_ready(
            topk_ef_sparse(x, e, k=k, block=bs))
        log(f"[B] topk_ef_sparse n={n}: {time.perf_counter() - t0:.4f}s "
            f"(includes compile; first observation, not a claim)")
        sel = comp.select(tot)
        check(_bitwise(vals.reshape(-1), sel.vals)
              and _bitwise(idx.reshape(-1), sel.idx)
              and _bitwise(ne, tot.at[sel.idx].set(0.0)),
              f"B: topk_ef_sparse == Compressor.select bitwise (n={n}, k={k})")
        hat, ne2 = topk_ef(x, e, k=k, block=bs)
        want = comp.compress(tot)
        check(_bitwise(hat, want) and _bitwise(ne2, tot - want),
              f"B: topk_ef == Compressor.compress bitwise (n={n}, k={k})")

    kw = dict(eta=0.5, beta1=0.9, beta2=0.99, eps=1e-3)
    for (clients, k), n in zip(((4, 32), (10, 1)), leaf_sizes):
        bs, nb = block_layout(n, 2048)
        N = nb * bs
        comp = make_compressor("blocktopk", k / bs, bs)
        keys = jax.random.split(jax.random.fold_in(key, clients), 6)
        sels = [comp.select(_rand(jax.random.fold_in(keys[0], c), N))
                for c in range(clients)]
        vals = jnp.stack([s.vals for s in sels])
        idx = jnp.stack([s.idx for s in sels])
        x, m = _rand(keys[1], N), _rand(keys[2], N, 0.1)
        v = jnp.abs(_rand(keys[3], N, 0.01))
        vh = v + jnp.abs(_rand(keys[4], N, 0.01))
        shape3 = (clients, nb, k)
        got = fedams_ingest(x, m, v, vh, vals.reshape(shape3),
                            idx.reshape(shape3), n_div=clients, block=bs,
                            **kw)
        want = jax.jit(lambda *a: ref.fedams_ingest_ref(
            *a, n_div=clients, block=bs, **kw))(
            x, m, v, vh, vals.reshape(shape3), idx.reshape(shape3))
        check(all(_bitwise(g, w) for g, w in zip(got, want)),
              f"B: fedams_ingest == fedams_ingest_ref bitwise "
              f"({clients} clients, k={k}, n={N})")
        fed = FedConfig(algorithm="fedcams", **kw)
        x2, st2 = jax.jit(lambda x, m, v, vh, vals, idx: server_update(
            fed, ServerState(m, v, vh, 0), x,
            server_aggregate_sparse(vals, idx, N, clients)))(
            x, m, v, vh, vals, idx)
        hits = np.bincount(np.asarray(idx).reshape(-1), minlength=N)
        lone = hits <= 1
        base = (x2, st2.m, st2.v, st2.vhat)
        check(all(_bitwise(np.asarray(g)[lone], np.asarray(b)[lone])
                  for g, b in zip(got, base)),
              f"B: fedams_ingest == two-pass baseline bitwise on the "
              f"{int(lone.sum())} coordinates at most one client selected "
              f"({clients} clients, k={k})")
        coll = ~lone
        log(f"[B] {int(coll.sum())} collided coordinates: max |dm| "
            f"{float(np.abs(np.asarray(got[1]) - np.asarray(st2.m))[coll].max(initial=0.0))}"
            f" (one reassociated sum, which the contract allows)")

    n = leaf_sizes[0]
    ks = jax.random.split(key, 5)
    x, m, d = _rand(ks[0], n), _rand(ks[1], n, 0.1), _rand(ks[2], n, 0.1)
    v = jnp.abs(_rand(ks[3], n, 0.01))
    vh = v + jnp.abs(_rand(ks[4], n, 0.01))
    for option in (1, 2):
        fed = FedConfig(algorithm="fedcams", option=option, **kw)
        got = fedams_update(x, m, v, vh, d, option=option, **kw)
        x2, st2 = jax.jit(lambda x, m, v, vh, d: server_update(
            fed, ServerState(m, v, vh, 0), x, d))(x, m, v, vh, d)
        check(all(_bitwise(g, w) for g, w in
                  zip(got, (x2, st2.m, st2.v, st2.vhat))),
              f"B: fedams_update == server_update bitwise (option {option}, "
              f"n={n})")


def trainer_one_round(seed: int):
    """One round of the jnp providers through the other entry point,
    ``FederatedTrainer`` on a one-chip mesh, configured as
    :func:`base_args` configures ``launch.train``."""
    import jax
    from repro.configs import FedConfig, TrainConfig
    from repro.configs.registry import get_arch
    from repro.core.api import FederatedTrainer
    from repro.data.synthetic import FederatedLMData
    from repro.launch.mesh import make_mesh
    from repro.models.model import Model

    cfg = get_arch("xlstm-350m").model
    fed = FedConfig(algorithm="fedcams", compressor="blocktopk",
                    compress_ratio=1 / 64, aggregation="sparse",
                    mesh_sparse_impl="jnp", fused_ingest="jnp",
                    local_steps=2, num_clients=1, client_axes=(),
                    eta=0.5, eta_l=0.05)
    train_cfg = TrainConfig(global_batch=8, seq_len=64, rounds=1,
                            remat_policy="none", seed=seed)
    trainer = FederatedTrainer(
        fed=fed, train=train_cfg, model=Model(cfg),
        mesh=make_mesh((1, 1), ("data", "model")),
        lm_data=FederatedLMData(num_clients=1, vocab_size=cfg.vocab_size,
                                seed=seed))
    t0 = time.perf_counter()
    hist = trainer.run(1, log=None)
    jax.block_until_ready(trainer._state)
    log(f"[C] FederatedTrainer, jnp providers, 1 round: loss "
        f"{hist[0]['loss']} in {time.perf_counter() - t0:.1f}s incl. "
        f"compile (first observation, not a claim)")
    return hist, trainer._state


def phase_entry_points(seed: int, kernel_hist):
    """The two entry points run the same round: FederatedTrainer and
    launch.train, both with the jnp providers, agree bit for bit."""
    hist, state = trainer_one_round(seed)
    trainer_loss, trainer_state = hist[0]["loss"], to_host(state)
    del state
    out = train(base_args(seed, 1) + ["--aggregation", "sparse",
                                       "--mesh-sparse-impl", "jnp",
                                       "--fused-ingest", "jnp"])
    report("C launch.train jnp, 1 round", out)
    diff = state_diff(trainer_state, to_host(out["state"]))
    check(out["history"][0]["loss"] == trainer_loss
          and all(n == 0 for n, _ in diff.values()),
          f"C: FederatedTrainer == launch.train bitwise, one round (loss "
          f"and state; differing elements per field {diff})")
    # where a kernel program and a jnp program part (not a contract): the
    # round-0 loss averages the K local steps' losses, before any kernel
    # runs — a difference there is the local phase compiled differently
    k_loss = kernel_hist[0]["loss"]
    log(f"[C] round-0 loss, kernel program (A) {k_loss} vs jnp program "
        f"{trainer_loss}: " + ("equal" if k_loss == trainer_loss else
                               "the local phase, which no kernel touches, "
                               "differs between the two programs; D "
                               "compares the providers on one shared "
                               "local phase"))


def phase_providers(seed: int, rounds: int, layers: int = 4):
    """Kernel providers against jnp providers from the same state and
    batch, every round, with the local phase SHARED: both round bodies sit
    in one program on the same inputs, so XLA computes the K local steps
    once and only the selection and ingest differ. Depth is cut to
    ``layers`` (published widths) so the input state and both output
    states fit one chip."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.configs import FedConfig, TrainConfig
    from repro.configs.registry import get_arch
    from repro.core.mesh import (build_fed_round, fed_batch_defs,
                                 fed_state_defs, init_fed_state,
                                 mesh_context, mesh_metric_specs)
    from repro.data.synthetic import FederatedLMData
    from repro.kernels.ops import KernelImpl
    from repro.launch.mesh import make_mesh
    from repro.models import params as pdefs
    from repro.models.model import Model

    cfg = dataclasses.replace(get_arch("xlstm-350m").model, num_layers=layers)
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    common = dict(algorithm="fedcams", compressor="blocktopk",
                  compress_ratio=1 / 64, aggregation="sparse",
                  local_steps=2, num_clients=1, client_axes=(), eta=0.5,
                  eta_l=0.05)
    fed_k = FedConfig(mesh_sparse_impl="kernel", fused_ingest="kernel",
                      **common)
    fed_j = FedConfig(mesh_sparse_impl="jnp", fused_ingest="jnp", **common)
    train_cfg = TrainConfig(global_batch=8, seq_len=64, rounds=rounds,
                            remat_policy="none", seed=seed)
    ctx = mesh_context(fed_k, mesh)
    round_k = build_fed_round(model, fed_k, train_cfg, ctx,
                              kernel_impl=KernelImpl())
    round_j = build_fed_round(model, fed_j, train_cfg, ctx)
    specs = lambda defs: jax.tree.map(lambda d: d.spec, defs,
                                      is_leaf=pdefs.is_def)
    ssp = specs(fed_state_defs(model, fed_k))
    msp = mesh_metric_specs(fed_k)
    both = jax.jit(jax.shard_map(
        lambda s, b, r: (round_k(s, b, r), round_j(s, b, r)), mesh=mesh,
        in_specs=(ssp, specs(fed_batch_defs(model, fed_k, train_cfg)), P()),
        out_specs=((ssp, msp), (ssp, msp)), check_vma=True))
    state = init_fed_state(model, fed_k, jax.random.PRNGKey(seed), mesh=mesh)
    data = FederatedLMData(num_clients=1, vocab_size=cfg.vocab_size,
                           seed=seed)
    for r in range(rounds):
        batch = {k: jnp.asarray(v) for k, v in
                 data.mesh_batch(r, 2, 8, 64).items()}
        (sk, mk), (sj, mj) = both(state, batch, jnp.int32(r))
        diff = state_diff(to_host(sk), to_host(sj))
        check(float(mk["loss"]) == float(mj["loss"])
              and all(n == 0 for n, _ in diff.values()),
              f"D: round {r}, {layers}-layer xlstm-350m, same state and "
              f"batch — kernel providers == jnp providers bitwise (loss "
              f"{float(mk['loss'])}; differing elements per field {diff})")
        state = sk
        del sj


# -- four chips ---------------------------------------------------------------


def state_bytes_per_device(state, devices):
    import jax
    per = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(state):
        for s in leaf.addressable_shards:
            per[s.device] += s.data.nbytes
    return [per[d] / GB for d in devices]


def phase_four_chip(seed: int, rounds: int, devices):
    sparse = train(base_args(seed, rounds, dp=4) + ["--aggregation", "sparse"])
    report("4 sparse", sparse)
    layout = state_bytes_per_device(sparse["state"], devices)
    peak_sparse = peaks(devices)
    log(f"[4] state GB per chip {layout}; peak GB per chip {peak_sparse}")
    sparse_losses = [h["loss"] for h in sparse["history"]]
    del sparse
    dense = train(base_args(seed, rounds, dp=4) + ["--aggregation", "dense"])
    report("4 dense", dense)
    peak_all = peaks(devices)
    log(f"[4] peak GB per chip after both {peak_all}")
    dense_losses = [h["loss"] for h in dense["history"]]
    # the two aggregations sum the same selected values, so only rounding
    # separates them — but they are two programs, and on one chip two
    # round programs already differed by 8e-5 relative in the round-0
    # loss, before any aggregation (the bf16 local phase compiles
    # differently); 1e-3 leaves room for that and still catches a wrong
    # aggregate, which moves the next round's loss by far more
    check(finite(dense["history"]) and all(
        abs(a - b) <= 1e-3 * abs(b) for a, b in zip(sparse_losses,
                                                    dense_losses)),
          f"4: sparse all_gather and dense psum losses agree to 1e-3 "
          f"relative ({sparse_losses} vs {dense_losses})")
    # every chip holds the same share of the state (params and server
    # state replicated, one client's EF row each), so no chip may peak
    # far above the others — the bug this guards is a state built whole
    # on chip 0
    spread = max(peak_all) - min(peak_all)
    check(max(layout) - min(layout) <= 0.01 * max(layout)
          and spread <= 0.1 * max(peak_all),
          f"4: per-chip peaks within the layout (state {max(layout):.2f} GB "
          f"per chip; peaks {min(peak_all):.2f}..{max(peak_all):.2f} GB)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip phase (needs 4 chips)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    devices = require_tpu()
    from repro.launch.cache import enable_compile_cache
    log(f"[setup] compile cache {enable_compile_cache()}; "
        f"{len(devices)} x {devices[0].device_kind}")
    if args.four_chip:
        if len(devices) != 4:
            raise SystemExit(f"--four-chip needs 4 chips, found "
                             f"{len(devices)}")
        run_phase("4", phase_four_chip, args.seed, 2, devices)
    else:
        from repro.configs.registry import get_arch
        from repro.models.model import Model
        defs = Model(get_arch("xlstm-350m").model).defs()
        hist = run_phase("A", phase_main_path, args.seed, args.rounds,
                         devices)
        run_phase("B", phase_kernels, args.seed, leaf_sizes(defs))
        if hist is not None:
            run_phase("C", phase_entry_points, args.seed, hist)
        run_phase("D", phase_providers, args.seed, args.rounds)
    log(f"[done] {time.perf_counter() - t_start:.0f}s wall; peak HBM GB per "
        f"device {peaks(devices)}")
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed: {FAILED}",
              file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


def leaf_sizes(defs):
    """The largest leaf's size and one layer's slice of a stacked layer
    leaf (``w_q`` of the first layer group)."""
    import math

    import jax
    from repro.models import params as pdefs
    sizes = [math.prod(d.shape) for d in
             jax.tree.leaves(defs, is_leaf=pdefs.is_def)]
    w_q = defs["stack"]["groups"]["l0"]["mix"]["w_q"]
    return max(sizes), math.prod(w_q.shape[1:])


if __name__ == "__main__":
    sys.exit(main())
