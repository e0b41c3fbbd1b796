"""The paper-faithful simulation backend (DESIGN.md §1).

``FedSim`` runs the paper's Algorithms 1 & 2 as pure-array simulation:
m clients (default 100), vmapped local updates (core/local.py rules),
*global-vector* compression exactly as the paper evaluates it. Runs on one
CPU device; powers the paper-faithful benchmarks and examples. The mesh
(production SPMD) backend lives in core/mesh.py; both compose the shared
EF/compress/wire stages from core/stages.py.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree

from repro.comm.faults import (FaultConfig, FaultInjector, FaultPlan,
                               corrupt_dense, corrupt_selection,
                               validate_dense, validate_selection)
from repro.configs.base import FedConfig
from repro.core.compressors import Compressor, make_compressor
from repro.core.local import (hetero_step_counts, local_lr, make_local_update,
                              run_local_steps)
from repro.core.server_opt import (FUSED_INGEST_GROUPS_DETAIL,
                                   init_server_state, server_ingest,
                                   server_update)
from repro.core.stages import (client_uplink, client_uplink_sparse,
                               ef_update_sparse, gamma_diagnostic,
                               resolve_fused_ingest, server_aggregate_sparse,
                               server_aggregate_sparse_grouped,
                               server_aggregate_sparse_masked,
                               server_aggregate_sparse_weighted,
                               server_downlink)


class SimState(NamedTuple):
    params: object            # pytree
    opt: object               # ServerState over flat vector
    errors: jax.Array         # (m, d) per-client EF errors — or, with
    # fed.ef_store, the (n, d) participating-cohort rows gathered for the
    # current round while the full store lives host-side (DESIGN.md
    # §scale-out)
    server_error: jax.Array   # (d,) server-side EF error (two-way mode)
    x_client: jax.Array       # (d,) model as clients see it (two-way mode)
    # Host-side Python ints, exact at any scale: fp32 accumulation is only
    # exact below 2^24, which a single dense round at d=11.2M blows through
    # (n·32·d ≈ 3.6e8 bits), silently freezing cumulative-bits plots — and
    # keeping them off-device means the round needs no device→host sync.
    bits: int                 # cumulative one-way communicated bits
    round: int


class _CoreState(NamedTuple):
    """The device-resident slice of :class:`SimState` — the jit/scan carry.

    ``bits``/``round`` stay host-side (see SimState); everything here is
    donated to the round executable (``donate_argnums``) so the (m, d)
    error-feedback buffer and the optimizer state update in place instead
    of being copied every round."""
    params: object
    opt: object
    errors: jax.Array
    server_error: jax.Array
    x_client: jax.Array


class FedSim:
    """Federated simulation over an arbitrary ``loss_fn(params, batch)``.

    The local phase runs the configured :class:`~repro.core.local.LocalUpdate`
    rule (``fed.local_opt``: plain SGD, heavy-ball momentum, or proximal
    SGD) under the per-round LR schedule (``fed.eta_l_decay``) and
    heterogeneous per-client step counts (``fed.local_steps_min``).

    With ``fed.wire=True`` every client delta is serialized to packed bytes
    (repro.comm.wire), timed through a simulated network
    (repro.comm.transport — pass ``network`` to customize links), and
    decoded server-side; error feedback tracks the decoded value, so the
    simulation is exact w.r.t. what the wire actually carried. Round
    metrics then include measured ``wire_bytes`` and simulated
    ``round_time_s`` next to the analytic ``bits``.

    For the top-k family the uplink defaults to the select-once sparse
    fast path (``fed.sparse_uplink``, DESIGN.md §3): the compacted
    ``(vals, idx)`` selection flows from compressor to server aggregate —
    in wire mode via the codec's bit-identical ``roundtrip_selection``
    shortcut, so the round never re-runs ``lax.top_k`` or materializes a
    dense per-client hat.
    """

    def __init__(self, loss_fn: Callable, fed: FedConfig,
                 compressor: Optional[Compressor] = None,
                 network: Optional[object] = None):
        self.loss_fn = loss_fn
        self.fed = fed
        self.rule = make_local_update(fed)
        if compressor is None and fed.algorithm == "fedcams":
            compressor = make_compressor(fed.compressor, fed.compress_ratio,
                                         fed.wire_block)
        self.comp = compressor if fed.algorithm == "fedcams" else None
        # select-once sparse uplink (DESIGN.md §3): auto-on whenever the
        # compressor has a compacted (vals, idx) form, forced by the knob
        self.sparse = (self.comp is not None
                       and self.comp.select is not None
                       if fed.sparse_uplink is None
                       else bool(fed.sparse_uplink))
        if self.sparse and (self.comp is None or self.comp.select is None):
            raise ValueError(
                "sparse_uplink=True needs a compressor with a .select "
                "(topk/blocktopk family); this one has none")
        n_round = fed.participating or fed.num_clients
        if fed.client_chunk and 0 < fed.client_chunk < n_round \
                and n_round % fed.client_chunk:
            raise ValueError(
                f"client_chunk={fed.client_chunk} must divide the "
                f"per-round client count n={n_round} — a silent fallback "
                f"to the full (n, d) vmap would defeat the memory bound")
        if fed.agg_groups > 1:
            # two-level aggregation (DESIGN.md §scale-out): groups merge
            # compacted selections, so the flat dense paths can't run it
            if not self.sparse:
                raise ValueError(
                    "FedConfig.agg_groups > 1 needs the select-once sparse "
                    "(vals, idx) uplink — this config resolved the dense "
                    "reference path (no compacted selection to group-merge)")
            if fed.client_chunk and 0 < fed.client_chunk < n_round \
                    and fed.client_chunk != n_round // fed.agg_groups:
                raise ValueError(
                    f"client_chunk={fed.client_chunk} and agg_groups="
                    f"{fed.agg_groups} both set: the chunk must equal the "
                    f"group size n//g={n_round // fed.agg_groups} so each "
                    f"scan step is exactly one group's tier-1 merge")
        # one-pass fused server ingest (DESIGN.md §3): the (vals, idx)
        # selection goes straight into the m/v/v̂/x update — needs the
        # block-grouped selection layout (blocktopk), no dense-aggregate
        # consumer (γ diagnostic), and the unchunked sparse path (the
        # chunked round accumulates a dense running scatter instead)
        chunked = bool(fed.client_chunk) and 0 < fed.client_chunk < n_round
        # fault tolerance (DESIGN.md §robustness): resolve the effective
        # FaultConfig — a bare fed.deadline_s means "deadline cutoff, no
        # injected faults" — and build the host-side deterministic injector
        fcfg = fed.fault
        if fed.deadline_s > 0:
            fcfg = (FaultConfig(deadline_s=fed.deadline_s) if fcfg is None
                    else dataclasses.replace(fcfg, deadline_s=fed.deadline_s))
        self.faults = (FaultInjector(fcfg, fed.num_clients)
                       if fcfg is not None else None)
        eligible = (self.sparse and self.comp is not None
                    and self.comp.name.startswith("blocktopk")
                    and not fed.track_gamma and not chunked
                    and fed.agg_groups <= 1 and self.faults is None)
        from repro.kernels.common import resolve_interpret
        self._fused = resolve_fused_ingest(
            fed, eligible=eligible, have_kernel=True,
            compiled=not resolve_interpret(None),
            detail="FedSim fuses only the unchunked sparse blocktopk "
                   "uplink with track_gamma=False (the γ diagnostic and "
                   "the client_chunk scan both consume a dense aggregate) "
                   "and no fault injection (the masked survivor aggregate "
                   "needs the unfused scatter path)"
                   + FUSED_INGEST_GROUPS_DETAIL)
        self._efs = None  # EFStore, created in init() once d is known
        self._round_fn = None
        self._scan_fn = None
        self._async_dispatch_fn = None
        self._async_flush_fn = None
        self._async = None
        self.codec = None
        self.network = None
        if network is not None and not fed.wire:
            raise ValueError(
                "a network was supplied but fed.wire is False — the "
                "transport simulation only runs in wire mode; set "
                "FedConfig(wire=True)")
        if fed.wire:
            from repro.comm import (CommLog, NetworkConfig, SimulatedNetwork,
                                    make_dense32_codec, make_wire_codec)
            name = fed.compressor if self.comp is not None else "dense32"
            self.codec = make_wire_codec(name, fed.compress_ratio,
                                         fed.wire_block, fed.wire_value_dtype)
            self._down_codec = (self.codec if fed.two_way
                                else make_dense32_codec())
            self.network = network or SimulatedNetwork(
                NetworkConfig(), fed.num_clients)
            self.comm_log = CommLog()
        if fed.async_buffer:
            # event-driven buffered rounds (DESIGN.md §11): the engine owns
            # the host-side event loop and drives the jitted dispatch/flush
            # steps below; config validation already pinned the supported
            # slice (wire + sparse uplink, no deadline/groups/ef_store)
            from repro.comm.async_engine import AsyncRoundEngine
            self._async = AsyncRoundEngine(self)

    def init(self, params) -> SimState:
        flat, self.unravel = ravel_pytree(params)
        d = flat.size
        self._d = d
        # the selection block layout (== the fused ingest / int8 quant
        # layout): block_layout clamps wire_block exactly like the
        # compressor will at select time
        from repro.core.compressors import block_layout
        bs, nb = block_layout(d, self.fed.wire_block)
        self._ingest_block = bs
        # valid index domain for server-side validation: selections carry
        # padded-tail indices in [d, nb*bs) that the scatter drops, so the
        # range check must accept the padded domain, not just [0, d)
        self._sel_domain = bs * nb
        m = self.fed.num_clients
        if self.fed.ef_store:
            # EF shard store (DESIGN.md §scale-out): the device buffer
            # holds only the participating cohort's rows; the full (m, d)
            # store lives host-side in lazily materialized numpy shards
            from repro.checkpoint.store import EFStore
            self._efs = EFStore(m, d)
            err_rows = self.fed.participating or m
        else:
            err_rows = m
        # copy the caller's params ONCE: the first round donates the state's
        # buffers, and consuming arrays the caller still owns would poison
        # any later use of their init pytree
        params = jax.tree.map(jnp.array, params)
        return SimState(
            params=params,
            opt=init_server_state(flat, self.fed.server_state_dtype,
                                  self._ingest_block),
            errors=jnp.zeros((err_rows, d), jnp.float32),
            server_error=jnp.zeros((d,), jnp.float32),
            x_client=flat,
            bits=0,
            round=0,
        )

    def _bits_per_round(self, n: int) -> int:
        """Analytic one-way bits for one round (exact host-side int)."""
        if self.comp is not None:
            return n * int(self.comp.bits_per_message(self._d))
        return n * 32 * self._d

    def _round_timing(self, idx_host, round_idx: int):
        """Simulated-network timing draw for one round (host-side numpy,
        deterministic in (seed, round)). Runs BEFORE the jitted round when
        faults are on — the deadline cutoff needs the per-client times to
        decide who is dead before aggregation."""
        if self.network is None:
            return None
        up = self.codec.nbytes(self._d)
        down = self._down_codec.nbytes(self._d)
        return self.network.round(idx_host, up, down, round_idx)

    def _record_timing(self, timing, finfo) -> dict:
        """Book one round's timing into the CommLog. With hierarchical
        aggregation the uplink is billed per tier: n client messages
        (tier 1, the codec bytes) plus g dense fp32 group partials pushed
        to the root (tier 2). A fault-tolerant round threads the
        injector's deadline-truncated wall-clock into the log (so
        ``sim_time_s == Σ round_time_s``) and bills uplink bytes only for
        the clients whose payload actually arrived — delivered-but-
        rejected clients still count (the wire carried their bytes); the
        full cohort's sends stay visible as the attempted diagnostic."""
        eff_time = delivered = None
        if finfo is not None:
            eff_time = finfo["round_time_s"]
            delivered = int(finfo["survivors"]) * self.codec.nbytes(self._d)
        g = self.fed.agg_groups
        tier2 = g * 4 * self._d if g > 1 else 0
        return self.comm_log.record(timing, tier2_bytes=tier2,
                                    round_time_s=eff_time,
                                    delivered_uplink_bytes=delivered)

    # -- one round ---------------------------------------------------------
    def round(self, state: SimState, client_batches, client_idx, rng, *,
              prefetch_idx=None):
        """client_batches: pytree with leading (n, K, ...); client_idx: (n,).

        The input state's device buffers are DONATED to the round
        executable (the (m, d) EF error buffer updates in place) — keep
        only the returned state.

        With ``fed.ef_store`` the round brackets the jitted body with the
        host-side EF shard store (DESIGN.md §scale-out): gather the
        cohort's rows to a dense (n, d) device block, run the round over
        row *positions*, scatter the updated rows back. ``prefetch_idx``
        (the NEXT round's client ids) starts the background gather for
        round r+1 right after this round is dispatched, so the host
        assembly overlaps the device compute."""
        if self._async is not None:
            raise ValueError(
                "fed.async_buffer routes training through the event-driven "
                "buffered engine, which consumes ALL staged cohorts in one "
                "call — use run_rounds(...) (FederatedTrainer.run stages "
                "this automatically)")
        if self._round_fn is None:
            self._round_fn = jax.jit(self._round_impl, donate_argnums=(0,))
        idx_host = np.asarray(client_idx)
        # transport runs between jitted rounds: byte counts are static per
        # codec, the timing draw is host-side numpy; the round index is the
        # host counter (no device sync). It runs BEFORE the round so the
        # fault injector can turn per-client times into a deadline mask.
        timing = self._round_timing(idx_host, state.round)
        fplan = finfo = None
        if self.faults is not None:
            fplan, finfo = self.faults.plan(idx_host, state.round, timing)
            fplan = FaultPlan(*(jnp.asarray(a) for a in fplan))
        if self._efs is not None:
            rows = self._efs.gather(idx_host)
            core = _CoreState(state.params, state.opt, jnp.asarray(rows),
                              state.server_error, state.x_client)
            # the round body indexes the (n, d) cohort block by position —
            # per-client rng/batches key off position already, so the math
            # per row is bit-identical to the resident (m, d) buffer
            pos_idx = jnp.arange(idx_host.size, dtype=jnp.int32)
            new_core, met = self._round_fn(core, client_batches, pos_idx,
                                           rng, jnp.int32(state.round),
                                           fplan)
            if prefetch_idx is not None:
                self._efs.prefetch(np.asarray(prefetch_idx))
            # np.asarray blocks on the round; the prefetch above overlaps it
            self._efs.scatter(idx_host, np.asarray(new_core.errors))
        else:
            new_core, met = self._round_fn(_CoreState(*state[:5]),
                                           client_batches, client_idx, rng,
                                           jnp.int32(state.round), fplan)
        bits = state.bits + self._bits_per_round(client_idx.shape[0])
        met = dict(met)
        met["bits"] = bits
        if timing is not None:
            met.update(self._record_timing(timing, finfo))
        if finfo is not None:
            met["crashed"] = finfo["crashed"]
            met["deadline_cut"] = finfo["deadline_cut"]
        return SimState(*new_core, bits=bits, round=state.round + 1), met

    # -- many rounds, one device program ------------------------------------
    def run_rounds(self, state: SimState, client_batches, client_idx, rngs):
        """Scan-driven multi-round execution: R rounds in one jitted
        ``lax.scan`` with donated carry — one dispatch and one host sync
        total, instead of R of each.

        ``client_batches``: pytree with leading (R, n, K, ...);
        ``client_idx``: (R, n); ``rngs``: PRNG keys with leading R.
        Returns ``(new_state, mets)`` with the same per-round metric dicts
        the :meth:`round` loop produces, bit-identical.

        With ``fed.ef_store`` the scan is replaced by a per-round loop:
        each round's cohort rows move host↔device around the jitted body,
        which a scan carry cannot express (the row set changes every
        round). The loop prefetches round r+1's rows while round r
        computes; metrics keep the exact :meth:`round` semantics."""
        R, n = int(client_idx.shape[0]), int(client_idx.shape[1])
        if self._async is not None:
            # async buffered engine (DESIGN.md §11): one metric dict per
            # FLUSH — ceil(deliveries / B) of them, not R
            return self._async.run(state, client_batches, client_idx, rngs)
        if self._efs is not None:
            st, mets = state, []
            for r in range(R):
                b_r = jax.tree.map(lambda x: x[r], client_batches)
                nxt = client_idx[r + 1] if r + 1 < R else None
                st, met = self.round(st, b_r, client_idx[r], rngs[r],
                                     prefetch_idx=nxt)
                mets.append(met)
            return st, mets
        if self._scan_fn is None:
            def scan_rounds(core, batches, idx, keys, rounds, fplans):
                def body(c, inp):
                    b, i, k, r, fp = inp
                    return self._round_impl(c, b, i, k, r, fp)
                return lax.scan(body, core,
                                (batches, idx, keys, rounds, fplans))
            self._scan_fn = jax.jit(scan_rounds, donate_argnums=(0,))
        idx_host = np.asarray(client_idx)
        # host-side transport + fault planning for all R rounds up front
        # (network.round is deterministic and idempotent per round index);
        # the plans stack into one (R, n)-leading FaultPlan the scan
        # consumes as xs — faults never force the loop path
        timings = [self._round_timing(idx_host[r], state.round + r)
                   for r in range(R)]
        fplans = None
        finfos = [None] * R
        if self.faults is not None:
            plans = []
            for r in range(R):
                p, finfos[r] = self.faults.plan(idx_host[r], state.round + r,
                                                timings[r])
                plans.append(p)
            fplans = FaultPlan(*(jnp.asarray(np.stack(leaf))
                                 for leaf in zip(*plans)))
        rounds_dev = state.round + jnp.arange(R, dtype=jnp.int32)
        new_core, stacked = self._scan_fn(_CoreState(*state[:5]),
                                          client_batches, client_idx, rngs,
                                          rounds_dev, fplans)
        stacked = jax.device_get(stacked)  # the single host sync
        bpr = self._bits_per_round(n)
        mets = []
        for r in range(R):
            met = {k: v[r] for k, v in stacked.items()}
            met["bits"] = state.bits + bpr * (r + 1)
            if timings[r] is not None:
                met.update(self._record_timing(timings[r], finfos[r]))
            if finfos[r] is not None:
                met["crashed"] = finfos[r]["crashed"]
                met["deadline_cut"] = finfos[r]["deadline_cut"]
            mets.append(met)
        new_state = SimState(*new_core, bits=state.bits + bpr * R,
                             round=state.round + R)
        return new_state, mets

    def _local_train(self, params, batches, eta_l, k_i=None):
        """K local steps of the configured rule for ONE client.
        batches: (K, ...); ``k_i`` (traced scalar) masks steps past this
        client's heterogeneous step count."""

        def grad_fn(p, b):
            (l, _), g = jax.value_and_grad(self.loss_fn, has_aux=True)(p, b)
            return l, g

        # unrolled (capped): K is static, and unrolling lets XLA fuse
        # across local steps instead of paying while-loop overhead — same
        # ops in the same order, numerics unchanged. The cap bounds program
        # size for large-K configs (the body is also nested inside the
        # run_rounds round scan).
        k = jax.tree.leaves(batches)[0].shape[0]
        return run_local_steps(self.rule, grad_fn, params, batches, eta_l,
                               k_i=k_i, unroll=min(k, 8))

    def _train_block(self, start, flat0, batches, rng, eta_l, k_blk=None):
        """Local training for a block of clients → ((c, d) deltas, losses)."""
        if k_blk is None:
            local, losses = jax.vmap(
                lambda b: self._local_train(start, b, eta_l))(batches)
        else:
            local, losses = jax.vmap(
                lambda b, ki: self._local_train(start, b, eta_l, ki))(
                    batches, k_blk)
        delta = jax.vmap(lambda p: ravel_pytree(p)[0])(local) - flat0[None, :]
        return delta, losses

    def _clients_block(self, start, flat0, batches, errs, pos, rng, eta_l,
                       k_blk=None):
        """Local training + uplink compression for a block of clients.

        ``batches``: (c, K, ...) pytree; ``errs``: (c, d) EF errors (ignored
        when no compressor); ``pos``: (c,) global positions in the round
        (the per-client RNG stream); ``k_blk``: (c,) heterogeneous step
        counts or None. Returns (hats, new_errs, delta, losses)."""
        delta, losses = self._train_block(start, flat0, batches, rng, eta_l,
                                          k_blk)
        hats, new_errs = client_uplink(self.comp, self.codec, flat0.size,
                                       rng, delta, errs, pos)
        return hats, new_errs, delta, losses

    def _sparse_uplink_block(self, errors, block_idx, start, flat0, batches,
                             pos, rng, eta_l, k_blk=None):
        """Train + select-once uplink for a block of clients, updating the
        (m, d) EF buffer in place (DESIGN.md §3): the buffer rows gain the
        deltas (they then hold the EF totals), the compacted selection is
        taken from those rows, and only the selected coordinates are
        rewritten with the post-wire residual — no dense per-client hat or
        error rebuild. Returns (errors, rx_vals, idx, tot_rows, delta,
        losses); ``tot_rows`` feeds the γ diagnostic and is dead code
        (eliminated by XLA) when ``track_gamma`` is off."""
        delta, losses = self._train_block(start, flat0, batches, rng, eta_l,
                                          k_blk)
        errors = errors.at[block_idx].add(delta)
        tot_rows = errors[block_idx]
        sel_vals, idx, rx_vals = client_uplink_sparse(
            self.comp, self.codec, flat0.size, rng, tot_rows, pos)
        errors = ef_update_sparse(errors, block_idx, idx, sel_vals, rx_vals)
        return errors, rx_vals, idx, tot_rows, delta, losses

    def _fault_round(self, core: _CoreState, client_batches, client_idx, rng,
                     round_idx, fplan: FaultPlan):
        """Fault-tolerant round (DESIGN.md §robustness): every client
        trains and uplinks as usual — the damage is in transit — then the
        server masks the aggregate down to validated survivors.

        Invariants:
          * the client books its EF residual against the CLEAN decoded
            value it sent; corruption happens after booking, so a client
            whose payload the server rejects (NACK) or who crashed gets
            its EF row rolled back to the stale pre-round value and
            repays the residual on rejoin (core/error_feedback.py);
          * validation runs BEFORE ingest: NaN/Inf and out-of-range
            indices zero the offender's contribution and drop it from
            the survivor count, so one poisoned payload cannot reach the
            FedAMS m/v/v̂ state;
          * with an all-ones survivor mask and corruption off this is
            bit-identical to :meth:`_round_impl` (regression-tested).
        """
        fed = self.fed
        fcfg = self.faults.cfg
        n = client_idx.shape[0]
        start = self.unravel(core.x_client)
        flat0 = core.x_client
        d = flat0.size
        pos = jnp.arange(n)
        eta_l = local_lr(fed, round_idx)
        k_all = hetero_step_counts(fed, rng, n)
        corrupting = fcfg.corrupt_prob > 0
        if self.sparse:
            old_rows = core.errors[client_idx]
            delta, losses = self._train_block(start, flat0, client_batches,
                                              rng, eta_l, k_all)
            tot = old_rows + delta
            sel_vals, sidx, rx_vals = client_uplink_sparse(
                self.comp, self.codec, d, rng, tot, pos)
            # client-side EF books the residual vs the CLEAN decoded value
            new_rows = jax.vmap(lambda t, i, r_: t.at[i].set(r_))(
                tot, sidx, sel_vals - rx_vals)
            rx, ridx = (corrupt_selection(rx_vals, sidx, fplan,
                                          fcfg.corrupt_mode)
                        if corrupting else (rx_vals, sidx))
            vvals, valid = validate_selection(rx, ridx, self._sel_domain,
                                              fcfg.max_update_norm)
            surv = fplan.survivors * valid
            errors = core.errors.at[client_idx].set(
                jnp.where(surv[:, None] > 0, new_rows, old_rows))
            agg = server_aggregate_sparse_masked(vvals, ridx, d, surv)
        else:
            errs = (core.errors[client_idx] if self.comp is not None
                    else jnp.zeros((n, 0), jnp.float32))
            hats, new_errs, delta, losses = self._clients_block(
                start, flat0, client_batches, errs, pos, rng, eta_l, k_all)
            rx = (corrupt_dense(hats, fplan, fcfg.corrupt_mode)
                  if corrupting else hats)
            truncated = (fplan.corrupt
                         if corrupting and fcfg.corrupt_mode == "truncate"
                         else None)
            vhats, valid = validate_dense(rx, fcfg.max_update_norm,
                                          truncated)
            surv = fplan.survivors * valid
            agg = jnp.sum(jnp.where(surv[:, None] > 0, vhats, 0.0),
                          axis=0) / jnp.maximum(jnp.sum(surv), 1.0)
            if self.comp is not None:
                errors = core.errors.at[client_idx].set(
                    jnp.where(surv[:, None] > 0, new_errs, errs))
            else:
                errors = core.errors
        loss = jnp.mean(losses)  # cohort mean — training happened on every
        # client whether or not its uplink survived
        xflat, _ = ravel_pytree(core.params)
        new_flat, opt = server_update(fed, core.opt, xflat, agg)
        x_client, server_error = server_downlink(
            fed, self.comp, self.codec, d, rng, new_flat, core.x_client,
            core.server_error)
        new_core = _CoreState(self.unravel(new_flat), opt, errors,
                              server_error, x_client)
        met = {"loss": loss, "gamma": jnp.zeros(()),
               "survivors": jnp.sum(surv),
               "rejected": jnp.sum(fplan.survivors * (1.0 - valid))}
        return new_core, met

    # -- async buffered engine steps (DESIGN.md §11) -------------------------
    def _ensure_async_fns(self):
        """Build the engine's two jitted steps on first use. Dispatch
        donates the (m, d) EF buffer; flush donates the server tuple —
        each updates in place across the host-side event loop."""
        if self._async_dispatch_fn is None:
            self._async_dispatch_fn = jax.jit(self._async_dispatch_impl,
                                              donate_argnums=(0,))
            self._async_flush_fn = jax.jit(self._async_flush_impl,
                                           donate_argnums=(0,))

    def _async_dispatch_impl(self, errors, x_client, client_batches,
                             client_idx, rng, round_idx,
                             fplan: Optional[FaultPlan] = None):
        """Client side of one async cohort: train + select-once sparse
        uplink against the CURRENT server model, EF booked at dispatch.

        The no-fault path is verbatim :meth:`_sparse_uplink_block` (the
        sync round's client half — bitwise the parity anchor); the fault
        path mirrors :meth:`_fault_round`'s client side: corruption
        happens after the EF books the clean residual, and a client whose
        payload will be rejected (or who crashed) keeps its stale EF row
        to repay on its next dispatch. Returns
        ``(errors, vals, idx, losses)`` — the payload the engine schedules
        for delivery."""
        fed = self.fed
        n = client_idx.shape[0]
        start = self.unravel(x_client)
        flat0 = x_client
        pos = jnp.arange(n)
        eta_l = local_lr(fed, round_idx)
        k_all = hetero_step_counts(fed, rng, n)
        if fplan is None:
            errors, rx_vals, sidx, _tot, _delta, losses = \
                self._sparse_uplink_block(errors, client_idx, start, flat0,
                                          client_batches, pos, rng, eta_l,
                                          k_all)
            return errors, rx_vals, sidx, losses
        fcfg = self.faults.cfg
        old_rows = errors[client_idx]
        delta, losses = self._train_block(start, flat0, client_batches, rng,
                                          eta_l, k_all)
        tot = old_rows + delta
        sel_vals, sidx, rx_vals = client_uplink_sparse(
            self.comp, self.codec, flat0.size, rng, tot, pos)
        new_rows = jax.vmap(lambda t, i, r_: t.at[i].set(r_))(
            tot, sidx, sel_vals - rx_vals)
        rx, ridx = (corrupt_selection(rx_vals, sidx, fplan,
                                      fcfg.corrupt_mode)
                    if fcfg.corrupt_prob > 0 else (rx_vals, sidx))
        # the validation verdict is deterministic in the payload, so the
        # dispatch-time verdict (EF rollback decision) and the flush-time
        # re-validation agree by construction
        _, valid = validate_selection(rx, ridx, self._sel_domain,
                                      fcfg.max_update_norm)
        surv = fplan.survivors * valid
        errors = errors.at[client_idx].set(
            jnp.where(surv[:, None] > 0, new_rows, old_rows))
        return errors, rx, ridx, losses

    def _async_flush_impl(self, core, vals, idx, w, fill, losses):
        """Server side of one buffered flush: ingest a fixed-shape (B, k)
        masked buffer through the validated weighted scatter (or the
        fused FedAMS ingest via an exact pre-scale).

        ``core``: (params, opt, server_error, x_client) — donated.
        ``w``: (B,) staleness weight × fill; ``fill``: (B,) 1.0 for
        occupied slots (a partial final flush leaves zeros). With faults
        armed the buffer re-validates before ingest (NaN/Inf or
        out-of-range payloads zero their weight). The fused path folds
        the weighted mean into the ingest's ``/B`` via
        ``scale = w·B/max(Σw, 1)`` — exactly 1.0 at unit weights, so the
        buffer==cohort anchor stays bitwise on the fused path too."""
        fed = self.fed
        params, opt, server_error, x_client = core
        d = self._d
        rejected = jnp.zeros(())
        if self.faults is not None:
            fcfg = self.faults.cfg
            vals, valid = validate_selection(vals, idx, self._sel_domain,
                                             fcfg.max_update_norm)
            rejected = jnp.sum(jnp.where(w > 0, 1.0 - valid, 0.0))
            w = w * valid
        # loss over ingested entries only (fill-masked mean)
        loss = jnp.sum(losses * fill) / jnp.maximum(jnp.sum(fill), 1.0)
        xflat, _ = ravel_pytree(params)
        if self._fused != "off":
            b = vals.shape[0]
            scale = w * (b / jnp.maximum(jnp.sum(w), 1.0))
            svals = jnp.where(w[:, None] > 0, vals, 0.0) * scale[:, None]
            new_flat, opt = server_ingest(fed, opt, xflat, svals, idx, b,
                                          block=self._ingest_block,
                                          impl=self._fused)
        else:
            agg = server_aggregate_sparse_weighted(vals, idx, d, w)
            new_flat, opt = server_update(fed, opt, xflat, agg)
        # two_way is rejected with async (configs.base), so the downlink
        # is the sync path's passthrough: clients see the exact new model
        met = {"loss": loss, "gamma": jnp.zeros(()), "rejected": rejected,
               "weight_sum": jnp.sum(w)}
        return (self.unravel(new_flat), opt, server_error, new_flat), met

    def _round_impl(self, core: _CoreState, client_batches, client_idx, rng,
                    round_idx, fplan: Optional[FaultPlan] = None):
        if fplan is not None:
            return self._fault_round(core, client_batches, client_idx, rng,
                                     round_idx, fplan)
        fed = self.fed
        n = client_idx.shape[0]
        start = self.unravel(core.x_client)  # what clients see (== params
        # unless two-way compression is on)
        flat0 = core.x_client
        d = flat0.size
        pos = jnp.arange(n)
        eta_l = local_lr(fed, round_idx)
        k_all = hetero_step_counts(fed, rng, n)  # None unless heterogeneous

        cc = fed.client_chunk
        if cc and 0 < cc < n and n % cc:  # trace-time n may differ from
            # the configured count __init__ validated against
            raise ValueError(
                f"client_chunk={cc} does not divide this round's client "
                f"count n={n} — refusing to silently fall back to the "
                f"full (n, d) vmap")
        if cc and 0 < cc < n:
            # client_chunk mode: scan the per-client train/compress/encode
            # pipeline over n/cc chunks, gathering/scattering each chunk's
            # EF slice inside the body and accumulating sums — peak
            # delta/hat/error working memory is (cc, d) instead of (n, d).
            # The sparse fast path accumulates each chunk's (vals, idx)
            # straight into the aggregate scatter, so the chunked round
            # never builds a dense hat either.
            shape_c = lambda x: x.reshape((n // cc, cc) + x.shape[1:])

            def body(carry, inp):
                b_c, i_c, p_c = inp
                errors, s_hat, s_tot, s_delta, s_loss = carry
                k_c = None if k_all is None else k_all[p_c]
                if self.sparse:
                    errors, vals, sidx, tot_c, delta, losses = \
                        self._sparse_uplink_block(
                            errors, i_c, start, flat0, b_c, p_c, rng,
                            eta_l, k_c)
                    if fed.agg_groups > 1:
                        # chunk == group (validated in __init__): merge
                        # this group's selections into a FRESH dense
                        # partial (tier 1) and hand the root the partial
                        # (tier 2 accumulate) — the group-partial
                        # association of the hierarchical mesh collective
                        s_hat = s_hat + jnp.zeros(d, jnp.float32).at[
                            sidx.reshape(-1)].add(vals.reshape(-1))
                    else:
                        s_hat = s_hat.at[sidx.reshape(-1)].add(
                            vals.reshape(-1))
                    s_tot = s_tot + jnp.sum(tot_c, axis=0)
                else:
                    e_c = (errors[i_c] if self.comp is not None
                           else jnp.zeros((cc, 0), jnp.float32))
                    hats, nerrs, delta, losses = self._clients_block(
                        start, flat0, b_c, e_c, p_c, rng, eta_l, k_c)
                    s_hat = s_hat + jnp.sum(hats, axis=0)
                    if self.comp is not None:
                        s_tot = s_tot + jnp.sum(delta + e_c, axis=0)
                        errors = errors.at[i_c].set(nerrs)
                s_delta = s_delta + jnp.sum(delta, axis=0)
                s_loss = s_loss + jnp.sum(losses)
                return (errors, s_hat, s_tot, s_delta, s_loss), None

            carry0 = (core.errors, jnp.zeros(d),
                      jnp.zeros(d if self.comp is not None else 0),
                      jnp.zeros(d), jnp.zeros(()))
            (errors, s_hat, s_tot, s_delta, s_loss), _ = lax.scan(
                body, carry0,
                (jax.tree.map(shape_c, client_batches),
                 shape_c(client_idx), shape_c(pos)))
            hats_mean, loss = s_hat / n, s_loss / n
            mean_tot, mean_delta = s_tot / n, s_delta / n
        elif self.sparse:
            errors, vals, sidx, tot_rows, delta, losses = \
                self._sparse_uplink_block(core.errors, client_idx, start,
                                          flat0, client_batches, pos, rng,
                                          eta_l, k_all)
            loss = jnp.mean(losses)
            if self._fused != "off":
                # one-pass fused ingest (DESIGN.md §3): the received
                # (vals, idx) selections go straight into the m/v/v̂/x
                # read-modify-write — no dense mean delta, no separate
                # server_update pass (bit-identical at fp32 state)
                xflat, _ = ravel_pytree(core.params)
                new_flat, opt = server_ingest(
                    fed, core.opt, xflat, vals, sidx, n,
                    block=self._ingest_block, impl=self._fused)
                x_client, server_error = server_downlink(
                    fed, self.comp, self.codec, d, rng, new_flat,
                    core.x_client, core.server_error)
                new_core = _CoreState(self.unravel(new_flat), opt, errors,
                                      server_error, x_client)
                return new_core, {"loss": loss, "gamma": jnp.zeros(())}
            hats_mean = (
                server_aggregate_sparse_grouped(vals, sidx, d, n,
                                                fed.agg_groups)
                if fed.agg_groups > 1
                else server_aggregate_sparse(vals, sidx, d, n))
            mean_tot = jnp.mean(tot_rows, axis=0)
            mean_delta = jnp.mean(delta, axis=0)
        else:
            errs = (core.errors[client_idx] if self.comp is not None
                    else jnp.zeros((n, 0), jnp.float32))
            hats, new_errs, delta, losses = self._clients_block(
                start, flat0, client_batches, errs, pos, rng, eta_l, k_all)
            hats_mean, loss = jnp.mean(hats, axis=0), jnp.mean(losses)
            if self.comp is not None:
                mean_tot = jnp.mean(delta + errs, axis=0)
                errors = core.errors.at[client_idx].set(new_errs)
            else:
                mean_tot = None
                errors = core.errors
            mean_delta = jnp.mean(delta, axis=0)

        agg = hats_mean
        # mean_tot/mean_delta feed only the diagnostic: with track_gamma
        # off, XLA dead-code-eliminates their (n, d) reductions entirely
        gamma = (gamma_diagnostic(self.comp, rng, mean_tot, agg, mean_delta)
                 if fed.track_gamma else jnp.zeros(()))

        # server update on the flat vector
        xflat, _ = ravel_pytree(core.params)
        new_flat, opt = server_update(fed, core.opt, xflat, agg)

        # beyond-paper: two-way (server->client) EF compression, appendix D
        x_client, server_error = server_downlink(
            fed, self.comp, self.codec, d, rng, new_flat, core.x_client,
            core.server_error)

        new_params = self.unravel(new_flat)
        new_core = _CoreState(new_params, opt, errors, server_error, x_client)
        return new_core, {"loss": loss, "gamma": gamma}
