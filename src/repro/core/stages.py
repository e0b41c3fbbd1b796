"""Shared round stages: the EF→compress→wire plumbing both backends use
(DESIGN.md §8).

Before the split these lived duplicated inside the rounds monolith — once
in the simulation's per-client block and once in the mesh ``fed_round``.
Each stage is a pure function over arrays; the backends (core/sim.py,
core/mesh.py) compose them around their own execution strategy (vmapped
flat vectors vs. shard_map collectives over pytree shards).

Simulation-side stages (flat per-client vectors):

* :func:`client_uplink`   — EF + compressor and/or wire codec for a block
  of client deltas; EF always tracks the value the wire actually carried.
* :func:`client_uplink_sparse` / :func:`server_aggregate_sparse` — the
  select-once sparse fast path (DESIGN.md §3): the compressor's
  :class:`~repro.core.compressors.Selection` stays a compacted
  ``(vals, idx)`` pair from client to server, the aggregate is an
  O(n·k + d) segment scatter instead of a dense (n, d) mean, and no dense
  per-client hat is ever materialized.
* :func:`server_downlink` — the beyond-paper two-way (server→client)
  EF-compressed downlink (paper appendix D).
* :func:`gamma_diagnostic` — the Assumption 4.17 γ measurement (Fig. 6).

Mesh-side stages (per-device pytree shards + client-axis collectives):

* :func:`agg_dense`         — paper-faithful dense psum aggregation.
* :func:`mesh_agg_strategy` — single resolver for which client-axis
  collective a config actually runs (mesh_uplink and the
  ``mesh_wire_bytes`` metric share it, so the byte accounting can never
  drift from the executed path).
* :func:`topk_select_tree`  — per-leaf select-once ``Selection`` + fused
  O(k)-scatter EF (the jnp sibling of
  ``KernelImpl.topk_select_tree``).
* :func:`sparse_topk_leaf`  — wire-size-true all_gather of one leaf's
  compacted ``(vals, idx)`` Selection + server scatter-add.
* :func:`sparse_topk_hier_leaf` — the two-level form (``agg_groups > 1``):
  member-axis Selection gather into a dense group partial, root consumes
  the g partials (DESIGN.md §scale-out).
* :func:`packed_sign_leaf`  — 1-bit/coordinate packed-sign all_gather.
* :func:`mesh_uplink`       — the full uplink: aggregation-strategy
  selection + masked EF + delta-dtype narrowing.

The mesh stages name the round's layers with :data:`LAYER_SCOPES`, as
``jax.named_scope`` s that never nest, so every compiled instruction of a
round sits under at most one of them and a profiler trace can be summed
by layer.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig
from repro.core.compressors import Compressor, Selection
from repro.core.error_feedback import ef_compress, ef_compress_masked
from repro.sharding.rules import ParallelContext

#: The mesh round's four layers as ``jax.named_scope`` names: the clients'
#: local steps and the delta they leave, client EF and selection (with the
#: EF row write), the client-axis collective, and what the server does
#: with what arrived (scatter-mean and the adaptive step). The scopes
#: never nest, since a trace reader matches them by substring.
LAYER_SCOPES = ("local_train", "ef_select", "uplink", "server_ingest")
LOCAL_TRAIN, EF_SELECT, UPLINK, SERVER_INGEST = LAYER_SCOPES


# ===========================================================================
# Simulation-side stages (flat per-client vectors)
# ===========================================================================


def client_uplink(comp: Optional[Compressor], codec, d: int, rng,
                  delta, errs, pos):
    """Local delta → (what the server receives, next EF error) for a block
    of clients.

    ``delta``: (c, d) flat deltas; ``errs``: (c, d) EF errors — ignored
    (and returned unchanged) when ``comp`` is None; ``pos``: (c,) global
    positions in the round (the per-client rng stream). Four cases:

    * comp + codec — wire mode: the EF total really goes through
      encode→decode; EF tracks the *decoded* value, so narrowed wire value
      dtypes stay exact in the error-feedback sense.
    * comp only — in-memory EF compression (``ef_compress``).
    * codec only — uncompressed algorithm over a dense32 wire.
    * neither — the delta passes through untouched.
    """
    if comp is not None:
        if codec is not None:
            def one(dd, ee, i):
                # the per-client key reaches the codec so a stochastic
                # wire format (randomized rounding) can't desync streams
                tot = dd + ee
                hat = codec.decode(
                    codec.encode(tot, jax.random.fold_in(rng, i)), d)
                return hat, tot - hat
        else:
            def one(dd, ee, i):
                return ef_compress(comp, dd, ee, jax.random.fold_in(rng, i))
        return jax.vmap(one)(delta, errs, pos)
    if codec is not None:
        hats = jax.vmap(lambda t: codec.decode(codec.encode(t), d))(delta)
    else:
        hats = delta
    return hats, errs


def client_uplink_sparse(comp: Compressor, codec, d: int, rng, tot, pos):
    """The select-once fast path for a block of clients (DESIGN.md §3).

    ``tot``: (c, d) EF totals (delta + carried error). The selection
    happens ONCE (``comp.select``) and the server-bound message stays the
    compacted ``(vals, idx)`` pair: no dense hat is built, and in wire mode
    the codec's ``roundtrip_selection`` narrows the values exactly the way
    the packed bytes would (bit-identical to the full encode→decode,
    property-tested) instead of re-running ``lax.top_k`` over the dense
    vector.

    Returns ``(sel_vals, idx, rx_vals)``, each (c, k): the selected values,
    their flat positions, and the values as the server receives them
    (``rx_vals == sel_vals`` on a float32 wire). The caller finishes error
    feedback with :func:`ef_update_sparse` — only selected coordinates
    change the error, so the EF write is an O(c·k) scatter, not a dense
    (c, d) rebuild.
    """
    def one(t, i):
        sel = comp.select(t, jax.random.fold_in(rng, i))
        rx = (codec.roundtrip_selection(sel, d) if codec is not None
              else sel)
        return sel.vals, sel.idx, rx.vals
    return jax.vmap(one)(tot, pos)


def ef_update_sparse(errors, rows, idx, sel_vals, rx_vals):
    """Finish sparse-path error feedback in place on the (m, d) buffer.

    ``errors`` rows already hold this round's totals (``err += delta``);
    only the selected coordinates change: they become ``sel_vals −
    rx_vals`` — exact zeros on a float32 wire (tot − tot), the quantization
    residual on narrowed wires — which equals the dense path's
    ``tot − hat`` coordinate for coordinate. ``rows``: (c,) client rows;
    ``idx``/``sel_vals``/``rx_vals``: (c, k). Padded-block positions
    (``idx >= d``, blockwise compressors) are dropped by the scatter,
    mirroring the dense pad-and-slice."""
    r = jnp.broadcast_to(rows[:, None], idx.shape)
    return errors.at[r, idx].set(sel_vals - rx_vals)


def server_aggregate_sparse(vals, idx, d: int, n: int):
    """Mean of n sparse client messages as one segment scatter-add over the
    (n·k) received entries — O(n·k + d) instead of the dense (n, d) mean.

    Collisions (a coordinate selected by several clients) accumulate in
    client order; floating-point reassociation against the dense mean's
    reduce is at most 1 ulp per colliding coordinate (see
    tests/test_sparse_uplink.py). Out-of-range padded indices are dropped.
    """
    return jnp.zeros(d, jnp.float32).at[idx.reshape(-1)].add(
        vals.reshape(-1)) / n


def server_aggregate_sparse_grouped(vals, idx, d: int, n: int, groups: int):
    """Two-tier mean of n sparse client messages (DESIGN.md §scale-out):
    the clients split into ``groups`` contiguous groups of n/g members;
    each group segment-scatters its members' ``(vals, idx)`` entries into
    a FRESH dense partial (tier 1 — the group-local merge), and the root
    sums the g partials (tier 2). Exactly the entries
    :func:`server_aggregate_sparse` consumes and the association the mesh's
    :func:`sparse_topk_hier_leaf` executes: within a group the scatter
    accumulates in member order, across groups the partial stack reduces —
    so vs the flat scatter only coordinates selected by clients in ≥2
    groups can reassociate, at ≤1 ulp each (the PR-4 collision analysis
    one level up)."""
    k = vals.shape[-1]
    vg = vals.reshape(groups, -1, k)
    ig = idx.reshape(groups, -1, k)
    partials = jax.vmap(
        lambda v, i: jnp.zeros(d, jnp.float32).at[i.reshape(-1)].add(
            v.reshape(-1)))(vg, ig)
    return jnp.sum(partials, axis=0) / n


def server_aggregate_sparse_masked(vals, idx, d: int, surv):
    """Survivor-masked sibling of :func:`server_aggregate_sparse`
    (DESIGN.md §robustness): mean of the sparse client messages over the
    SURVIVORS only — ``surv`` (n,) f32 is the fault-round survivor mask
    (delivered ∧ validated). Non-survivor entries are replaced by 0 with
    ``where`` (never multiply: a poisoned NaN times 0.0 is still NaN) and
    the divisor is the survivor count (min 1 — an all-dead round yields a
    zero aggregate, not a NaN). With an all-ones mask this is bit-identical
    to :func:`server_aggregate_sparse`: same scatter order, and the traced
    f32 count equals the Python ``n`` the unmasked path divides by."""
    contrib = jnp.where(surv[:, None] > 0, vals, 0.0)
    n_surv = jnp.maximum(jnp.sum(surv), 1.0)
    return jnp.zeros(d, jnp.float32).at[idx.reshape(-1)].add(
        contrib.reshape(-1)) / n_surv


def server_aggregate_sparse_weighted(vals, idx, d: int, w):
    """Weighted sibling of :func:`server_aggregate_sparse_masked` for the
    async buffered flush (DESIGN.md §11): ``w`` (n,) f32 carries each
    buffer entry's staleness weight × validity × fill mask, the aggregate
    is ``Σ_i w_i · vals_i / max(Σ w, 1)``. Zero-weight entries are
    replaced by 0 with ``where`` BEFORE the multiply (a rejected payload's
    NaN times 0.0 is still NaN). With all-ones ``w`` this is bit-identical
    to :func:`server_aggregate_sparse`: ``vals * 1.0`` is an IEEE
    identity, the scatter order is unchanged, and the traced f32 weight
    sum equals the Python ``n`` divisor (the parity anchor the async
    engine's acceptance flows through)."""
    contrib = jnp.where(w[:, None] > 0, vals, 0.0) * w[:, None]
    den = jnp.maximum(jnp.sum(w), 1.0)
    return jnp.zeros(d, jnp.float32).at[idx.reshape(-1)].add(
        contrib.reshape(-1)) / den


def server_downlink(fed: FedConfig, comp: Optional[Compressor], codec,
                    d: int, rng, new_flat, x_client, server_error):
    """Two-way (server→client) EF compression, paper appendix D.

    Returns ``(new_x_client, new_server_error)``: the model as clients will
    see it next round plus the carried server-side error. With ``two_way``
    off the clients see the exact new model and the error passes through."""
    if not (fed.two_way and comp is not None):
        return new_flat, server_error
    upd = new_flat - x_client
    tot = upd + server_error
    if codec is not None:  # downlink exercises the wire codec too
        hat = codec.decode(codec.encode(tot), d)
    else:
        hat = comp.compress(tot, jax.random.fold_in(rng, 10**6))
    return x_client + hat, tot - hat


def gamma_diagnostic(comp: Optional[Compressor], rng, mean_tot, agg,
                     mean_delta):
    """Assumption 4.17 diagnostic (paper Fig. 6):
    γ = ‖C(mean(Δ+e)) − mean(C(Δ+e))‖ / ‖mean(Δ)‖ — zero when
    uncompressed."""
    if comp is None:
        return jnp.zeros(())
    c_of_mean = comp.compress(mean_tot, jax.random.fold_in(rng, 999983))
    return (jnp.linalg.norm(c_of_mean - agg)
            / jnp.maximum(jnp.linalg.norm(mean_delta), 1e-12))


# ===========================================================================
# Mesh-side stages (per-device pytree shards, client-axis collectives)
# ===========================================================================


def agg_dense(hat_tree, my_mask, n_eff, ctx: ParallelContext,
              wire_dtype: str = "float32"):
    """Paper-faithful: dense psum over the client axes. ``wire_dtype``
    narrows the collective payload (bf16 halves client-axis bytes; the
    caller keeps error feedback exact by tracking the narrowed value)."""
    wd = jnp.dtype(wire_dtype)
    with jax.named_scope(UPLINK):
        contrib = jax.tree.map(
            lambda h: jnp.where(my_mask > 0, h, 0.0).astype(wd), hat_tree)
        summed = jax.tree.map(ctx.psum_clients, contrib)
    with jax.named_scope(SERVER_INGEST):
        return jax.tree.map(lambda c: c.astype(jnp.float32) / n_eff, summed)


def mesh_agg_strategy(fed: FedConfig) -> str:
    """Which client-axis collective the mesh round actually runs for this
    config: ``"sparse_topk"`` (compacted Selection all_gather),
    ``"sparse_topk_hier"`` (two-level: member-axis Selection gather into a
    dense group partial, then the root consumes the g partials —
    ``agg_groups > 1``, DESIGN.md §scale-out), ``"packed_sign"`` (1-bit
    packed gather), or ``"dense"`` (psum — including every fallback:
    non-fedcams algorithms, and sparse aggregation requested for a
    compressor with no compacted form). ``mesh_uplink`` and
    ``mesh_wire_bytes`` both resolve through here, so the wire accounting
    reports the path (and the tiers) that execute, never what the config
    merely asked for."""
    if fed.algorithm != "fedcams" or fed.aggregation != "sparse":
        return "dense"
    if fed.compressor in ("topk", "blocktopk"):
        return "sparse_topk_hier" if fed.agg_groups > 1 else "sparse_topk"
    if fed.compressor == "packedsign":
        return "packed_sign"
    return "dense"


def resolve_mesh_sparse_impl(fed: FedConfig, kernel_impl) -> str:
    """``fed.mesh_sparse_impl`` → the selection provider that will run:
    ``"kernel"`` (fused Pallas ``topk_ef_sparse`` via
    ``KernelImpl.topk_select_tree``) or ``"jnp"`` (``Compressor.select``).
    ``auto`` picks the kernel only when it compiles — on the CPU platform
    the interpreter loses to compiled XLA, so auto picks jnp even
    when a KernelImpl is supplied (it still serves the dense-hat
    ``ef_compress_tree`` path)."""
    impl = fed.mesh_sparse_impl
    if impl == "kernel":
        if kernel_impl is None:
            raise ValueError(
                "FedConfig.mesh_sparse_impl='kernel' but no kernel_impl "
                "was supplied — pass KernelImpl() to build_fed_round "
                "(launch/train.py: --use-kernels or --mesh-sparse-impl "
                "kernel)")
        return "kernel"
    if impl == "jnp":
        return "jnp"
    return ("kernel" if kernel_impl is not None and kernel_impl.compiled
            else "jnp")


def resolve_fused_ingest(fed: FedConfig, *, eligible: bool,
                         have_kernel: bool, compiled: bool,
                         detail: str = "") -> str:
    """``fed.fused_ingest`` → the ingest path that will run: ``"kernel"``
    (Pallas ``kernels.fedams_ingest``), ``"jnp"`` (the blocked-scatter
    fused path in ``server_opt.server_ingest_leaf``), or ``"off"`` (the
    two-pass ``server_aggregate_sparse`` + ``server_update`` baseline).

    Resolved at BUILD time, like :func:`resolve_mesh_sparse_impl`: the
    backend passes ``eligible`` (can this round fuse at all — sparse
    blocktopk uplink, no dense-aggregate consumers like the γ diagnostic,
    no client chunking / state sharding) and the resolver errors on a
    forced knob the build cannot honor instead of silently falling back.
    ``auto`` fuses whenever eligible, picking the kernel only where it
    compiles (TPU) — exactly the ``mesh_sparse_impl`` auto rule."""
    knob = fed.fused_ingest
    if knob == "off":
        return "off"
    if not eligible:
        if knob in ("kernel", "jnp"):
            raise ValueError(
                f"FedConfig.fused_ingest={knob!r} but this round cannot "
                f"fuse the server ingest: {detail}")
        return "off"
    if knob == "kernel" and not have_kernel:
        raise ValueError(
            "FedConfig.fused_ingest='kernel' but no kernel_impl was "
            "supplied — pass KernelImpl() to build_fed_round "
            "(launch/train.py: --use-kernels or --fused-ingest kernel)")
    if knob in ("kernel", "jnp"):
        return knob
    return "kernel" if (have_kernel and compiled) else "jnp"


def select_tree(select_leaf, delta, err, mask):
    """Shared select-once tree plumbing for BOTH selection providers (the
    jnp :func:`topk_select_tree` and the Pallas
    :meth:`repro.kernels.ops.KernelImpl.topk_select_tree` — the masking
    semantics live exactly once so the providers' documented bit-identity
    cannot drift). ``select_leaf(delta_leaf, err_leaf) -> (Selection,
    new_err_leaf)`` produces one leaf's compacted selection + fused EF
    residual; this wrapper applies the participation mask —
    non-participating clients (``mask == 0``) contribute zero values to
    the collective and keep their error unchanged.

    Returns ``(sel_tree, err_tree)`` where ``sel_tree`` has
    :class:`~repro.core.compressors.Selection` leaves (flat global ``idx``
    in the per-leaf zero-padded block domain)."""
    flat_d, tdef = jax.tree_util.tree_flatten(delta)
    flat_e = jax.tree_util.tree_leaves(err)
    sels, errs = [], []
    for dd, ee in zip(flat_d, flat_e):
        sel, ne = select_leaf(dd, ee)
        sels.append(Selection(vals=sel.vals * (mask > 0), idx=sel.idx))
        errs.append(jnp.where(mask > 0, ne, ee))
    return (jax.tree_util.tree_unflatten(tdef, sels),
            jax.tree_util.tree_unflatten(tdef, errs))


def topk_select_tree(comp: Compressor, delta, err, mask):
    """Select-once uplink for every leaf of this device's shard tree —
    the jnp sibling of :meth:`repro.kernels.ops.KernelImpl.topk_select_tree`
    (identical contract, bit-identical selection/EF).

    Per leaf: the EF total ``delta + err`` is selected ONCE
    (``comp.select`` — the same ``lax.top_k``/argmax semantics as the
    dense blocktopk path) and error feedback finishes as an O(k) scatter
    that zeroes exactly the selected coordinates (``tot − hat`` is ``tot``
    with the kept entries zeroed; no dense hat is ever built). Padded-tail
    indices (``idx >= d``) carry value 0.0 and are dropped by the
    scatter."""

    def leaf(dd, ee):
        tot = (dd + ee).reshape(-1)
        sel = comp.select(tot)
        return sel, tot.at[sel.idx].set(0.0).reshape(ee.shape)

    return select_tree(leaf, delta, err, mask)


def sparse_topk_leaf(sel: Selection, leaf, n_eff, ctx: ParallelContext):
    """Beyond-paper: aggregate one leaf from the clients' compacted
    Selections — the client-axis all_gather carries the already-selected
    ``(vals, idx)`` pairs (~2k words instead of d) and the server side is
    one scatter-add, exactly :func:`server_aggregate_sparse` over the
    gathered entries. The selection itself (and its fused EF residual)
    comes from the provider — :func:`topk_select_tree` or the Pallas
    ``KernelImpl.topk_select_tree`` — so no dense per-client hat exists on
    this path. ``leaf`` supplies the output shape; padded-tail indices
    (``idx >= leaf.size``) are dropped by the scatter."""
    d = leaf.size
    with jax.named_scope(UPLINK):
        g_vals = ctx.all_gather_clients(sel.vals[None], axis=0).reshape(-1)
        g_idx = ctx.all_gather_clients(sel.idx[None], axis=0).reshape(-1)
    with jax.named_scope(SERVER_INGEST):
        # NB: fresh zeros (replicated vma) — zeros_like(varying) would
        # taint the aggregate as client-varying.
        zeros = jnp.zeros(d, jnp.float32)
        agg = zeros.at[g_idx].add(g_vals) / n_eff
        return agg.reshape(leaf.shape)


def sparse_topk_leaf_validated(sel: Selection, leaf, mask,
                               ctx: ParallelContext, domain: int,
                               max_norm: float):
    """Fault-tolerant sibling of :func:`sparse_topk_leaf` (DESIGN.md
    §robustness): the gathered ``(vals, idx)`` selections pass the
    server's validation-before-ingest gate (NaN/Inf rejection, index-range
    check against the leaf's padded block ``domain``, optional per-client
    norm clip) and the scatter-mean runs over the combined survivor mask
    ``alive ∧ valid`` — an invalid payload contributes nothing and shrinks
    the divisor, so one poisoned client cannot corrupt the aggregate.

    ``mask``: (m,) f32 alive-mask (participation ∧ fault). Returns
    ``(agg, my_valid, rejected)``: the aggregated leaf, THIS device's own
    validity (every device sees the gathered copies, including its own
    damaged payload, so the NACK needs no extra collective), and the
    count of delivered-but-rejected clients for this leaf."""
    d = leaf.size
    from repro.comm.faults import validate_selection
    with jax.named_scope(UPLINK):
        g_vals = ctx.all_gather_clients(sel.vals[None], axis=0)   # (m, k)
        g_idx = ctx.all_gather_clients(sel.idx[None], axis=0)     # (m, k)
    with jax.named_scope(SERVER_INGEST):
        vvals, valid = validate_selection(g_vals, g_idx, domain, max_norm)
        surv = mask * valid
        contrib = jnp.where(surv[:, None] > 0, vvals, 0.0)
        zeros = jnp.zeros(d, jnp.float32)
        agg = zeros.at[g_idx.reshape(-1)].add(contrib.reshape(-1)) \
            / jnp.maximum(jnp.sum(surv), 1.0)
        rejected = jnp.sum(mask * (1.0 - valid))
        return agg.reshape(leaf.shape), valid[ctx.client_index()], rejected


def sparse_topk_hier_leaf(sel: Selection, leaf, n_eff,
                          ctx: ParallelContext):
    """Two-level aggregation of one leaf (DESIGN.md §scale-out). Tier 1:
    the member-axis all_gather carries each group's compacted ``(vals,
    idx)`` Selections (the same O(k)/client payload as
    :func:`sparse_topk_leaf`, but fanned into g independent gathers), and
    every group merges its members' entries into a dense partial with the
    same blocked scatter-add. Tier 2 — the root collective — gathers the g
    group partials over the group axis and sums them: the root consumes g
    messages of d words, independent of how many clients each group holds,
    instead of n·k client entries. Association matches
    :func:`server_aggregate_sparse_grouped` (within-group member-order
    scatter, then the partial-stack reduce)."""
    d = leaf.size
    with jax.named_scope(UPLINK):
        g_vals = ctx.all_gather_members(sel.vals[None], axis=0).reshape(-1)
        g_idx = ctx.all_gather_members(sel.idx[None], axis=0).reshape(-1)
    with jax.named_scope(SERVER_INGEST):
        # fresh zeros (replicated vma), exactly like sparse_topk_leaf
        partial = jnp.zeros(d, jnp.float32).at[g_idx].add(g_vals)
    with jax.named_scope(UPLINK):
        partials = ctx.all_gather_group_partials(partial[None],
                                                 axis=0)  # (g, d)
    with jax.named_scope(SERVER_INGEST):
        agg = jnp.sum(partials, axis=0) / n_eff
        return agg.reshape(leaf.shape)


def packed_sign_leaf(tot, my_mask, n_eff, ctx: ParallelContext):
    """Beyond-paper: scaled-sign with the sign bits packed 8->1 in uint8 for
    the client-axis all_gather (1 bit/coordinate on the wire)."""
    with jax.named_scope(EF_SELECT):
        flat = tot.reshape(-1)
        d = flat.size
        scale = jnp.mean(jnp.abs(flat)) * (my_mask > 0)
        bits = jnp.packbits((flat >= 0).astype(jnp.uint8))
        # sign(0) := +1 to match the packed bits (error feedback must track
        # the value the wire actually carried)
        hat = (jnp.mean(jnp.abs(flat))
               * jnp.where(flat >= 0, 1.0, -1.0)).reshape(tot.shape)
    with jax.named_scope(UPLINK):
        g_bits = ctx.all_gather_clients(bits[None], axis=0)      # (m, d/8)
        g_scale = ctx.all_gather_clients(scale[None], axis=0)    # (m,)
    with jax.named_scope(SERVER_INGEST):
        signs = (jnp.unpackbits(g_bits, axis=1)[:, :d].astype(jnp.float32)
                 * 2.0 - 1.0)
        agg = (g_scale[:, None] * signs).sum(0) / n_eff
        return agg.reshape(tot.shape), hat


def _split_pairs(pairs):
    is_pair = lambda x: isinstance(x, tuple)
    return (jax.tree.map(lambda pr: pr[0], pairs, is_leaf=is_pair),
            jax.tree.map(lambda pr: pr[1], pairs, is_leaf=is_pair))


_is_selection = lambda x: isinstance(x, Selection)


def mesh_uplink(fed: FedConfig, comp: Optional[Compressor],
                ctx: ParallelContext, kernel_impl, rng, delta, my_err,
                my_mask, n_eff):
    """This device's delta shards → (aggregated update, next EF error).

    Resolves the aggregation strategy (:func:`mesh_agg_strategy`,
    DESIGN.md §3) — dense psum, compacted-Selection gather, or packed-sign
    gather — applies masked error feedback, and narrows the dense
    collective to ``fed.delta_dtype`` with EF tracking the narrowed value.

    On the sparse top-k strategy the selection happens ONCE per leaf
    (``fed.mesh_sparse_impl``: the fused Pallas kernel emits the compacted
    ``(vals, idx)`` block and the EF residual in one HBM pass; the jnp
    fallback is ``Compressor.select`` + an O(k) EF scatter — bit-identical
    selection either way), and the client-axis collective carries that
    Selection, never a dense hat."""
    if comp is None:
        return agg_dense(delta, my_mask, n_eff, ctx, fed.delta_dtype), my_err

    strategy = mesh_agg_strategy(fed)
    if strategy == "packed_sign":
        with jax.named_scope(EF_SELECT):
            tot = jax.tree.map(lambda dd, ee: dd + ee, delta, my_err)
        agg, hat = _split_pairs(jax.tree.map(
            lambda t: packed_sign_leaf(t, my_mask, n_eff, ctx), tot))
        with jax.named_scope(EF_SELECT):
            new_err = jax.tree.map(
                lambda t, h, eo: jnp.where(my_mask > 0, t - h, eo),
                tot, hat, my_err)
        return agg, new_err

    if strategy in ("sparse_topk", "sparse_topk_hier"):
        with jax.named_scope(EF_SELECT):
            if resolve_mesh_sparse_impl(fed, kernel_impl) == "kernel":
                sels, new_err = kernel_impl.topk_select_tree(
                    comp.ratio, delta, my_err, my_mask)
            else:
                sels, new_err = topk_select_tree(comp, delta, my_err,
                                                 my_mask)
        # selection and EF are identical across tiers — only the collective
        # topology differs (flat gather vs member-gather + group partials)
        leaf_fn = (sparse_topk_hier_leaf if strategy == "sparse_topk_hier"
                   else sparse_topk_leaf)
        agg = jax.tree.map(
            lambda s, lf: leaf_fn(s, lf, n_eff, ctx),
            sels, delta, is_leaf=_is_selection)
        return agg, new_err

    with jax.named_scope(EF_SELECT):
        if kernel_impl is not None:
            hat, new_err = kernel_impl.ef_compress_tree(comp, delta, my_err,
                                                        my_mask)
        else:
            hat, new_err = ef_compress_masked(comp, delta, my_err, my_mask,
                                              jax.random.fold_in(rng, 2))
        if fed.delta_dtype != "float32":
            # error feedback must track the value actually sent
            wd = jnp.dtype(fed.delta_dtype)
            hat_tx = jax.tree.map(
                lambda h: h.astype(wd).astype(jnp.float32), hat)
            new_err = jax.tree.map(
                lambda d, e, h: jnp.where(my_mask > 0, d + e - h, e),
                delta, my_err, hat_tx)
            hat = hat_tx
    return agg_dense(hat, my_mask, n_eff, ctx, fed.delta_dtype), new_err
