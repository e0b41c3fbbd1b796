"""Subprocess side of the fault-tolerant mesh tests (tests/test_faults.py).

Runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` via
``tests/conftest.py::run_forced_devices``; never collected by pytest.
Reuses the deterministic single-leaf fixture from
``mesh_parity_harness`` (quantized targets, reassociation-free local
phase) so every comparison below holds at the bit level:

* **all-ones parity** — a ``FaultConfig()`` with nothing enabled must be
  loss/params/errors-bitwise against the fault-free build of the same
  round (the masked aggregation path costs nothing when nobody fails);
* **stale-then-repay** — a ``crash_trace`` outage leaves the dead
  client's EF row bitwise untouched, and on rejoin the uplink total is
  ``stale + delta``: verified against a zero-residual twin run (same
  jitted program, so the round-3 delta is bit-identical between the two
  runs) on the complement of both selection supports, where the EF rows
  expose the totals directly;
* **corruption NACK** — NaN-poisoned payloads are rejected before
  ingest: server state stays finite, and exactly the rejected clients'
  EF rows roll back to their pre-round values.
"""
from __future__ import annotations

import json

import numpy as np

from mesh_parity_harness import BC, K, M, ParityModel, _round_targets

ETA, ETA_L = 0.25, 0.0625
RATIO = 1.0 / 8.0


def _fed(fault=None, **kw):
    from repro.configs.base import FedConfig
    return FedConfig(algorithm="fedcams", compressor="blocktopk",
                     aggregation="sparse", compress_ratio=RATIO,
                     local_steps=K, num_clients=M, eta=ETA, eta_l=ETA_L,
                     client_axes=("data",), track_gamma=False,
                     fault=fault, **kw)


def _run(fed, rounds, edit_errors_row0_at=None):
    """Run ``rounds`` mesh fed_rounds; returns per-round numpy snapshots.
    ``edit_errors_row0_at=r`` zeroes client 0's EF row right before round
    ``r`` (the zero-residual twin of the repayment check)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import TrainConfig
    from repro.core.mesh import (build_fed_round, fed_batch_defs,
                                 fed_state_defs, init_fed_state,
                                 mesh_metric_specs)
    from repro.launch.mesh import make_mesh
    from repro.models import params as pdefs
    from repro.sharding.rules import ParallelContext

    model = ParityModel()
    train = TrainConfig(global_batch=M * BC, seq_len=1, remat_policy="none")
    mesh = make_mesh((M,), ("data",))
    ctx = ParallelContext(client_axes=fed.client_axes, num_clients=M)
    sdefs = fed_state_defs(model, fed)
    ssp = jax.tree.map(lambda d: d.spec, sdefs, is_leaf=pdefs.is_def)
    bsp = jax.tree.map(lambda d: d.spec, fed_batch_defs(model, fed, train),
                       is_leaf=pdefs.is_def)
    rnd = jax.jit(jax.shard_map(
        build_fed_round(model, fed, train, ctx), mesh=mesh,
        in_specs=(ssp, bsp, P()), out_specs=(ssp, mesh_metric_specs(fed))))
    state = init_fed_state(model, fed, jax.random.PRNGKey(0))
    out = []
    for r in range(rounds):
        if edit_errors_row0_at == r:
            err = np.array(state.errors["w"])
            err[0] = 0.0
            state = state._replace(errors={"w": jnp.asarray(err)})
        state, met = rnd(state, {"t": jnp.asarray(_round_targets(r))},
                         jnp.int32(r))
        out.append(dict(
            params=np.asarray(state.params["w"]),
            errors=np.asarray(state.errors["w"]),
            met={k: float(v) for k, v in met.items()}))
    return out


def run_all() -> dict:
    from repro.comm.faults import FaultConfig

    results = {}

    # 1. all-ones fault plan == fault-free build, bitwise
    base = _run(_fed(), 3)
    par = _run(_fed(fault=FaultConfig()), 3)
    results["parity"] = {
        "loss_bitwise": all(b["met"]["loss"] == p["met"]["loss"]
                            for b, p in zip(base, par)),
        "params_bitwise": all((b["params"] == p["params"]).all()
                              for b, p in zip(base, par)),
        "errors_bitwise": all((b["errors"] == p["errors"]).all()
                              for b, p in zip(base, par)),
        "survivors": [p["met"]["survivors"] for p in par],
    }

    # 2. scheduled outage: stale residual, then bitwise repayment on rejoin
    fault = FaultConfig(crash_trace=((0, 1, 3),))
    runa = _run(_fed(fault=fault), 4)
    runz = _run(_fed(fault=fault), 4, edit_errors_row0_at=3)
    stale = runa[0]["errors"][0]
    ea, ez = runa[3]["errors"][0], runz[3]["errors"][0]
    # EF rows are the uplink totals with exactly the selected coordinates
    # zeroed; off both supports row A must be (stale + delta) and row Z
    # must be delta — the same IEEE add the round computed in-trace
    off = (ea != 0.0) & (ez != 0.0)
    results["rejoin"] = {
        "stale_r1_bitwise": bool((runa[1]["errors"][0] == stale).all()),
        "stale_r2_bitwise": bool((runa[2]["errors"][0] == stale).all()),
        "others_moved_r1": bool(
            (runa[1]["errors"][1:] != runa[0]["errors"][1:]).any()),
        "off_support_count": int(off.sum()),
        "repay_bitwise": bool((ea[off] == (stale[off] + ez[off])).all()),
        "selection_shifted_by_residual": bool(
            ((ea == 0.0) != (ez == 0.0)).any()),
        "survivors": [r["met"]["survivors"] for r in runa],
    }

    # 3. NaN corruption: reject-before-ingest + EF NACK rollback
    runc = _run(_fed(fault=FaultConfig(corrupt_prob=0.6, corrupt_mode="nan",
                                       seed=3)), 3)
    rejected = [r["met"]["rejected"] for r in runc]
    nack_matches = []
    prev_err = np.zeros_like(runc[0]["errors"])
    for r, row in enumerate(runc):
        stale_rows = int((row["errors"] == prev_err).all(axis=1).sum())
        nack_matches.append(stale_rows == int(rejected[r]))
        prev_err = row["errors"]
    results["corruption"] = {
        "rejected": rejected,
        "any_rejected": any(x > 0 for x in rejected),
        "state_finite": bool(np.isfinite(runc[-1]["params"]).all()
                             and np.isfinite(runc[-1]["errors"]).all()),
        "loss_finite": all(np.isfinite(r["met"]["loss"]) for r in runc),
        "nack_rows_match_rejected": all(nack_matches),
    }
    return results


if __name__ == "__main__":
    print(json.dumps(run_all()))
