"""High-level facade: ``FederatedTrainer`` wires data, model/loss, the
paper's algorithm and checkpointing into a train() loop — the 10-line entry
point the examples and external users drive.

Two backends, selected by ``mesh``:
  * ``mesh=None``  — the pure simulation path (FedSim): arbitrary client
    count, exact paper semantics, single device.
  * ``mesh=...``   — the SPMD mesh path (shard_map fed_round): clients are
    mesh-axis indices with TP-sharded replicas.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedConfig, TrainConfig
from repro.core.mesh import init_fed_state, jit_fed_round
from repro.core.sim import FedSim
from repro.core.sampling import sample_clients


@dataclass
class FederatedTrainer:
    fed: FedConfig
    train: TrainConfig = field(default_factory=TrainConfig)
    # simulation backend
    loss_fn: Optional[Callable] = None          # (params, batch) -> (loss, aux)
    init_params: Optional[object] = None
    data: Optional[object] = None                # needs .round_batches(...)
    # mesh backend
    model: Optional[object] = None               # repro.models.Model
    mesh: Optional[object] = None
    lm_data: Optional[object] = None             # needs .mesh_batch(...)
    # wire mode (fed.wire=True): optional custom repro.comm.SimulatedNetwork
    network: Optional[object] = None

    def __post_init__(self):
        self.history: List[Dict] = []
        if self.mesh is None:
            assert self.loss_fn is not None and self.init_params is not None
            self._sim = FedSim(self.loss_fn, self.fed, network=self.network)
            self._state = self._sim.init(self.init_params)
        else:
            if self.network is not None:
                raise ValueError(
                    "network= is a simulation-backend (mesh=None) feature; "
                    "the mesh path reports measured wire_up_bytes but does "
                    "not simulate transport")
            if self.fed.async_buffer:
                raise ValueError(
                    "fed.async_buffer is a simulation-backend (mesh=None) "
                    "feature — the event-driven buffered engine drives "
                    "FedSim's transport simulation (DESIGN.md §11)")
            tp = dict(zip(self.mesh.axis_names,
                          self.mesh.devices.shape)).get("model", 1)
            assert self.model is not None and self.model.tp == tp
            from repro.kernels.ops import default_kernel_impl
            # the compiled kernels where they compile (TPU), jnp elsewhere
            self._kernel_impl = default_kernel_impl()
            # state buffers are donated: FedMeshState (params, opt moments,
            # per-client EF errors) updates in place round over round
            self._step = jit_fed_round(self.model, self.fed, self.train,
                                       self.mesh,
                                       kernel_impl=self._kernel_impl)
            self._scan_step = None
            self._state = init_fed_state(self.model, self.fed,
                                         jax.random.PRNGKey(self.train.seed),
                                         mesh=self.mesh)

    @property
    def params(self):
        return self._state.params

    def _mesh_scan_step(self):
        """Lazily build the scan-driven mesh step: R rounds of stacked
        batches/seeds scanned inside one shard_map (jit retraces per R)."""
        if self._scan_step is None:
            self._scan_step = jit_fed_round(
                self.model, self.fed, self.train, self.mesh,
                kernel_impl=self._kernel_impl, scan=True)
        return self._scan_step

    def _stage_sim_rounds(self, rng, r0: int, count: int, batch_size: int):
        """Host-side staging for ``count`` rounds: the same rng stream and
        data order the per-round loop consumes, stacked with leading R."""
        n = self.fed.participating or self.fed.num_clients
        idxs, keys, batches = [], [], []
        for r in range(r0, r0 + count):
            rng, k1, k2 = jax.random.split(rng, 3)
            idx = np.asarray(sample_clients(k1, self.fed.num_clients, n))
            batches.append(self.data.round_batches(
                idx, r, self.fed.local_steps, batch_size))
            idxs.append(idx)
            keys.append(k2)
        stacked = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)),
                               *batches)
        return rng, stacked, jnp.asarray(np.stack(idxs)), jnp.stack(keys)

    def run(self, rounds: Optional[int] = None, *, batch_size: int = 20,
            scan_rounds: int = 0,
            log: Optional[Callable[[str], None]] = print):
        """Train for ``rounds``. With ``scan_rounds=R > 1`` the driver
        stages R rounds of client indices and batches at a time and runs
        them as one on-device ``lax.scan`` (one dispatch + one metrics sync
        per R rounds, bit-identical history); otherwise one jitted call per
        round."""
        rounds = rounds or self.train.rounds
        rng = jax.random.PRNGKey(self.train.seed + 1)
        t0 = time.time()
        if self.mesh is None and self.fed.async_buffer:
            # the async buffered engine consumes ALL staged cohorts in one
            # run_rounds call (DESIGN.md §11) — its flush count need not
            # equal the staged cohort count, so the whole run is one chunk;
            # ``rounds`` then means dispatched cohorts, history rows are
            # flushes (max(·, 2) keeps the single-round case on the staged
            # path, which the engine requires)
            scan_rounds = max(rounds, 2)

        def record(met, r):
            rec = {k: float(v) for k, v in met.items()}
            rec["round"] = r
            self.history.append(rec)
            if log and (r % self.train.log_every == 0 or r == rounds - 1):
                log(f"round {r:4d}  loss {rec['loss']:8.4f}  "
                    f"({time.time() - t0:.1f}s)")

        if scan_rounds and scan_rounds > 1:
            r = 0
            while r < rounds:
                chunk = min(scan_rounds, rounds - r)
                if self.mesh is None:
                    rng, batches, idx, keys = self._stage_sim_rounds(
                        rng, r, chunk, batch_size)
                    self._state, mets = self._sim.run_rounds(
                        self._state, batches, idx, keys)
                else:
                    from repro.core.mesh import stage_mesh_rounds
                    batches, seeds = stage_mesh_rounds(
                        self.lm_data, r, chunk, self.fed.local_steps,
                        self.train.global_batch, self.train.seq_len)
                    self._state, stacked = self._mesh_scan_step()(
                        self._state, batches, seeds)
                    stacked = jax.device_get(stacked)
                    mets = [{k: v[i] for k, v in stacked.items()}
                            for i in range(chunk)]
                for i, met in enumerate(mets):
                    record(met, r + i)
                r += chunk
                ce = self.train.checkpoint_every
                if ce and any(rr % ce == 0 and rr > 0
                              for rr in range(r - chunk, r)):
                    # only chunk-boundary states exist under scan: snapshot
                    # once per chunk that crossed a checkpoint round
                    self.save(f"ckpt_round{r - 1}")
            return self.history

        for r in range(rounds):
            if self.mesh is None:
                rng, k1, k2 = jax.random.split(rng, 3)
                n = self.fed.participating or self.fed.num_clients
                idx = np.asarray(sample_clients(k1, self.fed.num_clients, n))
                raw = self.data.round_batches(idx, r, self.fed.local_steps,
                                              batch_size)
                self._state, met = self._sim.round(
                    self._state, jax.tree.map(jnp.asarray, raw),
                    jnp.asarray(idx), k2)
            else:
                raw = self.lm_data.mesh_batch(r, self.fed.local_steps,
                                              self.train.global_batch,
                                              self.train.seq_len)
                self._state, met = self._step(
                    self._state, {k: jnp.asarray(v) for k, v in raw.items()},
                    jnp.int32(r))
            record(met, r)
            if (self.train.checkpoint_every
                    and r % self.train.checkpoint_every == 0 and r > 0):
                self.save(f"ckpt_round{r}")
        return self.history

    def save(self, path: str):
        from repro.checkpoint import save_pytree
        save_pytree(path, jax.device_get(self._state._asdict()),
                    {"round": len(self.history), "algo": self.fed.algorithm})
