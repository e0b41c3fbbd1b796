"""The production mesh backend (DESIGN.md §1, §3, §5).

``build_fed_round`` returns the per-device SPMD round body (shard_map):
each index of the client axes IS one client holding a tensor-parallel
model replica; FedCAMS compression applies to the client-axis collective
(dense psum or the beyond-paper sparse/packed aggregation — DESIGN.md §3).
Per-client error-feedback state lives sharded on the client axes. The
local phase runs the configured core/local.py rule; the uplink composes
the shared core/stages.py mesh stages. The paper-faithful simulation
backend lives in core/sim.py.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm.faults import (FaultPlan, corrupt_selection,
                               mesh_corruption_plan, mesh_fault_mask)
from repro.configs.base import FedConfig, TrainConfig
from repro.core.compressors import Selection, block_layout, make_compressor
from repro.core.local import (hetero_step_counts, local_lr, make_local_update,
                              run_local_steps)
from repro.core.sampling import participation_mask
from repro.core.server_opt import (ServerState, server_ingest_tree,
                                   server_update)
from repro.core.stages import (EF_SELECT, LOCAL_TRAIN, SERVER_INGEST, UPLINK,
                               mesh_agg_strategy, mesh_uplink,
                               resolve_fused_ingest,
                               resolve_mesh_sparse_impl,
                               sparse_topk_leaf_validated, topk_select_tree)
from repro.models import params as pdefs
from repro.sharding.rules import ParallelContext


class FedMeshState(NamedTuple):
    params: object     # pytree, TP-sharded
    m: object          # server momentum    (fp32, like params)
    v: object          # server variance
    vhat: object       # max-stabilized variance
    errors: object     # per-client EF errors: leading client dim
    round: jax.Array


def client_batch_axes(fed: FedConfig) -> Tuple[str, ...]:
    """Mesh axes the global batch is sharded over."""
    axes = tuple(fed.client_axes)
    if "data" not in axes:
        axes = axes + ("data",)
    return axes


def state_shard_axes(fed: FedConfig):
    """Mesh axes the server state shards over (ZeRO mode)."""
    return tuple(fed.client_axes) if fed.client_axes else ("data",)


def state_shard_dim(dref: pdefs.ParamDef, shards: int):
    """First dim of a leaf that can host the server-state shard, or None."""
    if shards <= 1:
        return None
    for i, (size, sp) in enumerate(zip(dref.shape, dref.spec)):
        if sp is None and size % shards == 0 and size >= shards:
            return i
    return None


def fed_state_defs(model, fed: FedConfig):
    """ParamDef tree for the full federated state (GLOBAL shapes)."""
    par = model.defs()

    def fp32(dref: pdefs.ParamDef) -> pdefs.ParamDef:
        import dataclasses
        return dataclasses.replace(dref, dtype="float32")

    def opt_leaf(dref: pdefs.ParamDef) -> pdefs.ParamDef:
        import dataclasses
        dref = fp32(dref)
        if fed.shard_server_state:
            sd = state_shard_dim(dref, fed.state_shards)
            if sd is not None:
                axes = state_shard_axes(fed)
                spec = list(dref.spec)
                spec[sd] = axes[0] if len(axes) == 1 else tuple(axes)
                dref = dataclasses.replace(dref, spec=P(*spec))
        return dref

    def client_stacked(dref: pdefs.ParamDef) -> pdefs.ParamDef:
        import dataclasses
        if not fed.client_axes:
            ax = None
        elif len(fed.client_axes) == 1:
            ax = fed.client_axes[0]
        else:
            ax = tuple(fed.client_axes)
        return dataclasses.replace(
            dref, shape=(fed.num_clients,) + tuple(dref.shape),
            spec=P(ax, *dref.spec), dtype="float32")

    opt = jax.tree.map(opt_leaf, par, is_leaf=pdefs.is_def)
    # second-moment storage dtype (m always stays fp32): bf16 halves the
    # v/v̂ HBM residency; int8-blockscale has no mesh ParamDef form
    if fed.server_state_dtype == "int8":
        raise ValueError(
            "FedConfig.server_state_dtype='int8' is simulation-only — the "
            "blockscale QuantState layout has no mesh ParamDef form; use "
            "'bfloat16' on the mesh backend")
    if fed.server_state_dtype == "bfloat16":
        import dataclasses
        second = jax.tree.map(
            lambda dref: dataclasses.replace(dref, dtype="bfloat16"),
            opt, is_leaf=pdefs.is_def)
    else:
        second = opt
    errors = jax.tree.map(client_stacked, par, is_leaf=pdefs.is_def)
    return FedMeshState(
        params=par, m=opt, v=second, vhat=second, errors=errors,
        round=pdefs.ParamDef((), P(), dtype="int32", init="zeros"))


def init_fed_state(model, fed: FedConfig, rng, mesh=None) -> FedMeshState:
    """The round-0 federated state. With ``mesh`` it is built under jit
    with the state's own shardings as ``out_shardings``, so every device
    materializes only its shard (no device ever holds the whole state)."""
    defs = fed_state_defs(model, fed)

    def init(key):
        params = pdefs.init_params(defs.params, key)
        zeros = lambda t: jax.tree.map(
            lambda d: jnp.zeros(d.shape, jnp.dtype(d.dtype)), t,
            is_leaf=pdefs.is_def)
        return FedMeshState(params=params, m=zeros(defs.m), v=zeros(defs.v),
                            vhat=zeros(defs.vhat), errors=zeros(defs.errors),
                            round=jnp.zeros((), jnp.int32))

    if mesh is None:
        return init(rng)
    shardings = jax.tree.map(lambda d: NamedSharding(mesh, d.spec), defs,
                             is_leaf=pdefs.is_def)
    return jax.jit(init, out_shardings=shardings)(rng)


def mesh_context(fed: FedConfig, mesh,
                 tp_collective: str = "psum") -> ParallelContext:
    """The :class:`ParallelContext` of a federated round on ``mesh``. Every
    mesh axis is named even at size 1: the vma typing then proves the
    round's outputs replicated over it. Without client axes (one client),
    ``"data"`` is within-client data parallelism."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    hierarchical = "data" not in fed.client_axes and "data" in sizes
    return ParallelContext(
        model_axis="model" if "model" in sizes else None,
        tp=sizes.get("model", 1),
        data_axis="data" if hierarchical else None,
        dp=sizes["data"] if hierarchical else 1,
        client_axes=fed.client_axes, num_clients=fed.num_clients,
        tp_collective=tp_collective)


def _sharded_server_update(fed: FedConfig, st: ServerState, params, agg,
                           model, ctx: ParallelContext):
    """ZeRO-style server step: each index along the state-shard axes owns a
    slice of (m, v, v̂); it updates its slice of x from its slice of the
    aggregate and the refreshed params are all-gathered back (invariant vma).
    Leaves too small to shard stay replicated and update normally."""
    axes = state_shard_axes(fed)
    shards = fed.state_shards
    # linear index along the shard axes
    idx = 0
    for ax in axes:
        idx = idx * lax.psum(1, ax) + lax.axis_index(ax)

    defs = model.defs()
    dims = jax.tree.map(lambda d: state_shard_dim(d, shards), defs,
                        is_leaf=pdefs.is_def)

    def take(leaf, sd):
        if sd is None:
            return leaf
        chunk = leaf.shape[sd] // shards
        return lax.dynamic_slice_in_dim(leaf, idx * chunk, chunk, axis=sd)

    p_sh = jax.tree.map(take, params, dims)
    agg_sh = jax.tree.map(take, agg, dims)
    st_sh = ServerState(m=st.m, v=st.v, vhat=st.vhat, t=st.t)  # already shards
    newp_sh, new_st = server_update(fed, st_sh, p_sh, agg_sh)

    def gather(newp, oldp, sd):
        if sd is None:
            return newp
        x = newp
        for ax in axes:
            try:
                from jax._src.lax.parallel import all_gather_invariant
                x = all_gather_invariant(x, ax, axis=sd, tiled=True)
            except ImportError:  # pragma: no cover
                x = lax.all_gather(x, ax, axis=sd, tiled=True)
        return x.astype(oldp.dtype)

    new_params = jax.tree.map(gather, newp_sh, params, dims)
    return new_params, new_st


# -- the round ---------------------------------------------------------------


def leaf_wire_bytes(fed: FedConfig, dl: int, block: int = 2048) -> int:
    """Per-client collective payload bytes for ONE leaf of ``dl`` local
    elements, resolved through the same :func:`~repro.core.stages.
    mesh_agg_strategy` the round executes — so every fallback (non-fedcams,
    sparse aggregation with a compressor that has no compacted form) is
    billed as the dense psum it actually runs:

    * ``sparse_topk`` / ``sparse_topk_hier`` — the gathered Selection: an
      int32 global index + fp32 value per kept coordinate (8 bytes each),
      ``nb·kb`` entries in the leaf's padded block layout — exactly the
      two arrays ``stages.sparse_topk_leaf`` /
      ``stages.sparse_topk_hier_leaf`` all_gather (regression-tested
      against the traced collective operands in tests/test_mesh_parity.py).
      On the hierarchical strategy this is the TIER-1 (client → group)
      payload; the tier-2 group partial is :func:`leaf_tier2_bytes`.
    * ``packed_sign``  — the 8→1 packed sign bits + one fp32 scale.
    * ``dense``        — ``delta_dtype`` words for every element.
    """
    from repro.core.compressors import block_layout
    strategy = mesh_agg_strategy(fed)
    if strategy in ("sparse_topk", "sparse_topk_hier"):
        bs, nb = block_layout(dl, block)
        kb = max(1, int(round(fed.compress_ratio * bs)))
        return nb * kb * 8                # int32 index + fp32 value
    if strategy == "packed_sign":
        return (dl + 7) // 8 + 4          # 1 bit/coord + fp32 scale
    return dl * jnp.dtype(fed.delta_dtype).itemsize


def leaf_tier2_bytes(fed: FedConfig, dl: int) -> int:
    """Per-GROUP root-collective payload bytes for one leaf of ``dl`` local
    elements: the dense fp32 group partial ``sparse_topk_hier_leaf``
    gathers over the group axis. Zero on every flat strategy — the root
    tier only exists when the hierarchical collective actually runs."""
    if mesh_agg_strategy(fed) == "sparse_topk_hier":
        return dl * 4                     # fp32 partial, independent of n
    return 0


def mesh_wire_bytes(fed: FedConfig, delta_tree, block: int = 2048,
                    tp: int = 1) -> int:
    """Measured per-client contribution bytes for one mesh round's
    client-axis collective: the sum of :func:`leaf_wire_bytes` over the
    local shard tree. (Collectives carry no per-message header, unlike the
    comm.wire point-to-point codecs.)

    ``delta_tree`` holds this device's *local* shards; every one of the
    client's ``tp`` model-parallel devices pushes its own payload into the
    client-axis collective (model-replicated leaves included — each device
    sends its copy), so the client's wire traffic is the local total × tp.

    On the hierarchical strategy this is the TIER-1 (client → group)
    contribution; :func:`mesh_wire_bytes_tiers` gives both tiers.
    """
    total = sum(leaf_wire_bytes(fed, int(np.prod(leaf.shape)), block)
                for leaf in jax.tree.leaves(delta_tree))
    return total * max(tp, 1)


def mesh_wire_bytes_tiers(fed: FedConfig, delta_tree, block: int = 2048,
                          tp: int = 1) -> dict:
    """Per-tier uplink bytes for one mesh round, resolved through the
    executed :func:`~repro.core.stages.mesh_agg_strategy` like everything
    else: ``tier1`` is the per-CLIENT selection payload
    (:func:`mesh_wire_bytes` — every one of m clients pushes it), ``tier2``
    the per-GROUP dense partial the root consumes (g pushes, independent
    of the member count; 0 on flat strategies). The round's
    ``wire_up_bytes`` metric is ``m·tier1 + g·tier2`` — billing the tiers
    that actually run."""
    tier2 = sum(leaf_tier2_bytes(fed, int(np.prod(leaf.shape)))
                for leaf in jax.tree.leaves(delta_tree))
    return {"tier1": mesh_wire_bytes(fed, delta_tree, block, tp),
            "tier2": tier2 * max(tp, 1)}


def build_fed_round(model, fed: FedConfig, train: TrainConfig,
                    ctx: ParallelContext, *, chunk: int = 2048,
                    kernel_impl: Optional[object] = None):
    """Returns fed_round(state, batch, seed) — the per-device SPMD function
    (wrap in shard_map + jit via launch.train / launch.dryrun). Its
    operations carry the layer scopes of
    :data:`repro.core.stages.LAYER_SCOPES`, each under at most one; the
    participation mask and the loss's mean stay outside them."""
    # On the mesh, deltas are per-leaf shards (billions of elements for the
    # large archs): global top-k is ill-defined and lax.top_k overflows int32
    # indices, so "topk" means the blockwise TPU kernel semantics here
    # (DESIGN.md §3; contraction bound unchanged). Exact global top-k lives
    # in the FedSim simulation path.
    comp_name = "blocktopk" if fed.compressor == "topk" else fed.compressor
    if fed.ef_store:
        raise ValueError(
            "FedConfig.ef_store is FedSim-only — the mesh backend already "
            "shards per-client EF state over the client axes (one row per "
            "client-axis device); there is no resident (m, d) buffer to "
            "stream")
    strategy = mesh_agg_strategy(fed)
    # fault tolerance (DESIGN.md §robustness): the mesh draws its crash
    # mask in-trace from the shared round rng — every device must agree on
    # who died without host round-trips — and damages/validates payloads
    # around the gathered Selection collective
    fcfg = fed.fault
    if fed.deadline_s > 0 or (fcfg is not None and fcfg.deadline_s > 0):
        raise ValueError(
            "deadline_s is FedSim wire-mode only — the mesh backend has "
            "no transport clock to cut against; model stragglers as "
            "crashes (FaultConfig.crash_prob / crash_trace) on the mesh")
    validating = fcfg is not None and (fcfg.corrupt_prob > 0
                                       or fcfg.max_update_norm > 0)
    if validating and strategy != "sparse_topk":
        raise ValueError(
            f"FaultConfig corruption/validation on the mesh needs the "
            f"flat compacted-Selection collective (strategy "
            f"'sparse_topk'), but this config resolves {strategy!r} — "
            f"the validation-before-ingest gate inspects gathered "
            f"(vals, idx) payloads, which the dense psum / hierarchical "
            f"partials never materialize per client")
    if fed.agg_groups > 1 and strategy != "sparse_topk_hier":
        raise ValueError(
            f"FedConfig.agg_groups={fed.agg_groups} but this config "
            f"resolves the {strategy!r} aggregation strategy — the two-"
            f"level collective only exists for the compacted-Selection "
            f"path (fedcams + aggregation='sparse' + topk/blocktopk)")
    if strategy == "sparse_topk_hier":
        # the FIRST client axis is the group axis (sharding.rules); the
        # launch site sizes it to agg_groups when building the mesh
        if len(fed.client_axes) < 2:
            raise ValueError(
                f"agg_groups={fed.agg_groups} needs >= 2 client axes — "
                f"the first enumerates the groups, the rest the members "
                f"(e.g. client_axes=('cgroup', 'data')); got "
                f"{fed.client_axes!r}")
        if fed.num_clients % fed.agg_groups:
            raise ValueError(
                f"agg_groups={fed.agg_groups} must divide the client-axis "
                f"size m={fed.num_clients} (the mesh reshapes the client "
                f"axis into (groups, members))")
    # One-pass fused ingest (DESIGN.md §3): resolved at build time like the
    # selection provider. Eligible only on the FLAT compacted-Selection
    # strategy (the gathered (vals, idx) feed the ingest directly) without
    # state sharding (the fused pass owns the whole replicated update).
    from repro.core.server_opt import FUSED_INGEST_GROUPS_DETAIL
    fused = resolve_fused_ingest(
        fed,
        eligible=(strategy == "sparse_topk"
                  and not (fed.shard_server_state and fed.state_shards > 1)
                  and fcfg is None),
        have_kernel=kernel_impl is not None,
        compiled=kernel_impl is not None and kernel_impl.compiled,
        detail="the mesh fuses only the sparse_topk aggregation strategy "
               "(fedcams + aggregation='sparse' + topk/blocktopk) without "
               "shard_server_state or fault injection (the masked "
               "survivor aggregate needs the unfused gather path)"
               + FUSED_INGEST_GROUPS_DETAIL)
    # One block layout for the whole sparse path: when the kernel provider
    # will select OR the kernel ingest will consume, the jnp compressor,
    # the kernels, and the wire metric all use the kernel's block — layout
    # mismatches would silently break the kernel/jnp bit-identity and the
    # metric==payload invariant.
    sparse_block = 2048
    if strategy in ("sparse_topk", "sparse_topk_hier"):
        # resolve at build time, not inside the traced round: 'kernel'
        # without a KernelImpl has nothing to select with
        if (resolve_mesh_sparse_impl(fed, kernel_impl) == "kernel"
                or fused == "kernel"):
            sparse_block = kernel_impl.block
    comp = (make_compressor(comp_name, fed.compress_ratio, sparse_block)
            if fed.algorithm == "fedcams" else None)
    rule = make_local_update(fed)
    m_clients = fed.num_clients
    n_part = fed.participating or m_clients
    hierarchical = "data" not in fed.client_axes  # within-client DP on "data"

    def local_loss(p, b):
        return model.loss(p, b, ctx, remat_policy=train.remat_policy,
                          chunk=chunk)

    # TP gradient correctness relies on shard_map's varying-manual-axes
    # tracking (check_vma=True at every launch-site shard_map): jax then
    # transposes the forward psums correctly, so gradients of both sharded
    # and replicated parameters are exact — verified against the tp=1 model
    # in tests/test_sharding.py.

    def fed_round(state: FedMeshState, batch, seed):
        params = state.params

        # Clients must diverge during local training: mark the replicated
        # global params as VARYING over the client axes (lax.pvary — a
        # vma-type cast, no communication) so shard_map's vma autodiff does
        # NOT sum gradients across clients. In hierarchical mode the "data"
        # axis stays replicated, so the automatic gradient psum over "data"
        # implements within-client data parallelism (we rescale sum->mean).
        def _pvary(t):
            if not fed.client_axes:
                return t
            return jax.tree.map(
                lambda x: lax.pvary(x, tuple(fed.client_axes)), t)

        local0 = _pvary(params)

        # shared randomness -> identical draws on every device; also feeds
        # participation and the heterogeneous-K draw below
        rng = jax.random.fold_in(jax.random.PRNGKey(0), seed)

        def grad_fn(p, b):
            (l, _), g = jax.value_and_grad(local_loss, has_aux=True)(p, b)
            if hierarchical:
                g = jax.tree.map(lambda x: x / ctx.dp, g)
            # pre-cast to param dtype so the rule's update math runs in the
            # param dtype exactly as the pre-split step did
            g = jax.tree.map(lambda x, gg: gg.astype(x.dtype), p, g)
            return l, g

        with jax.named_scope(LOCAL_TRAIN):
            eta_l = local_lr(fed, state.round)
            k_all = hetero_step_counts(fed, rng, m_clients)
            k_i = None if k_all is None else k_all[ctx.client_index()]
            local, loss_local = run_local_steps(rule, grad_fn, local0, batch,
                                                eta_l, k_i=k_i)
            delta = jax.tree.map(lambda a, b_: (a - b_).astype(jnp.float32),
                                 local, local0)

        # participation (same mask on every device via the shared rng)
        mask = participation_mask(jax.random.fold_in(rng, 1), m_clients, n_part)
        if fcfg is not None:
            # crashed clients drop out of the round exactly like non-
            # participants: zero contribution, stale EF row (the drop
            # semantics core/error_feedback.py documents). n_eff becomes
            # the traced survivor count — with no faults drawn it equals
            # n_part bit-exactly, so the disabled path stays bit-identical.
            mask = mask * mesh_fault_mask(fcfg, rng, m_clients, state.round)
            n_eff = jnp.maximum(jnp.sum(mask), 1.0)
        else:
            n_eff = float(n_part)
        my_mask = mask[ctx.client_index()]

        with jax.named_scope(EF_SELECT):
            # local client slice
            my_err = jax.tree.map(lambda e: e[0], state.errors)
        st = ServerState(m=state.m, v=state.v, vhat=state.vhat, t=state.round)

        def _server_step(agg):
            # server update (replicated elementwise math on sharded leaves;
            # the ZeRO form's all-gather of the new params is the server's)
            with jax.named_scope(SERVER_INGEST):
                if kernel_impl is not None and fed.algorithm in (
                        "fedams", "fedcams", "fedamsgrad"):
                    return kernel_impl.fedams_update_tree(fed, st, params,
                                                          agg)
                if fed.shard_server_state and fed.state_shards > 1:
                    return _sharded_server_update(fed, st, params, agg,
                                                  model, ctx)
                return server_update(fed, st, params, agg)

        rejected = jnp.zeros(())
        if fused != "off":
            # one-pass fused ingest: select once (same provider resolution
            # as mesh_uplink's sparse branch), all_gather the compacted
            # Selections (identical collective + payload to
            # sparse_topk_leaf), and run scatter-mean + FedAMS update in a
            # single read-modify-write over the optimizer state — no dense
            # mean delta is materialized (bit-identical at fp32 state); the
            # ingest scopes each leaf's gathers and its pass apart
            with jax.named_scope(EF_SELECT):
                if resolve_mesh_sparse_impl(fed, kernel_impl) == "kernel":
                    sels, new_err = kernel_impl.topk_select_tree(
                        comp.ratio, delta, my_err, my_mask)
                else:
                    sels, new_err = topk_select_tree(comp, delta, my_err,
                                                     my_mask)
            gather = lambda a: ctx.all_gather_clients(a[None], axis=0)
            if fused == "kernel":
                new_params, new_st = kernel_impl.fedams_ingest_tree(
                    fed, st, params, sels, n_eff, gather)
            else:
                new_params, new_st = server_ingest_tree(
                    fed, st, params, sels, n_eff, gather,
                    block=sparse_block, impl="jnp")
        elif validating:
            # fault-tolerant sparse round: select once (same provider as
            # mesh_uplink's sparse branch), damage this device's OWN
            # payload in transit (every device computes the same shared
            # corruption plan, so the gathered copies — including the
            # sender's — all show the damage), validate server-side, and
            # aggregate over alive ∧ valid. A rejected client is NACKed:
            # its EF row rolls back to the stale pre-round value, the
            # same drop semantics core/error_feedback.py documents.
            with jax.named_scope(EF_SELECT):
                if resolve_mesh_sparse_impl(fed, kernel_impl) == "kernel":
                    sels, new_err = kernel_impl.topk_select_tree(
                        comp.ratio, delta, my_err, my_mask)
                else:
                    sels, new_err = topk_select_tree(comp, delta, my_err,
                                                     my_mask)
            with jax.named_scope(UPLINK):
                plan = mesh_corruption_plan(fcfg, rng, m_clients)
                ci = ctx.client_index()
                myplan = jax.tree.map(lambda a: a[ci][None], plan)

            def leaf_fault(s, lf):
                with jax.named_scope(UPLINK):     # damage in transit
                    cv, cidx = corrupt_selection(s.vals[None], s.idx[None],
                                                 myplan, fcfg.corrupt_mode)
                bs, nb = block_layout(lf.size, sparse_block)
                return sparse_topk_leaf_validated(
                    Selection(vals=cv[0], idx=cidx[0]), lf, mask, ctx,
                    bs * nb, fcfg.max_update_norm)

            is_sel = lambda x: isinstance(x, Selection)
            outs = jax.tree.map(leaf_fault, sels, delta, is_leaf=is_sel)
            is_t = lambda x: isinstance(x, tuple)
            agg = jax.tree.map(lambda t: t[0], outs, is_leaf=is_t)
            valid_leaves = [t[1] for t in
                            jax.tree.leaves(outs, is_leaf=is_t)]
            rejected = jax.tree.leaves(outs, is_leaf=is_t)[0][2]
            # a client survives only if EVERY leaf validated — one damaged
            # leaf NACKs the whole client update (EF rolls back, so the
            # full residual repays on the next clean round)
            with jax.named_scope(EF_SELECT):
                my_valid = valid_leaves[0]
                for v in valid_leaves[1:]:
                    my_valid = my_valid * v
                new_err = jax.tree.map(
                    lambda ne, eo: jnp.where(my_valid > 0, ne, eo),
                    new_err, my_err)
            new_params, new_st = _server_step(agg)
        else:
            agg, new_err = mesh_uplink(fed, comp, ctx, kernel_impl, rng,
                                       delta, my_err, my_mask, n_eff)
            new_params, new_st = _server_step(agg)

        with jax.named_scope(EF_SELECT):
            errors = jax.tree.map(lambda e, ne: e.at[0].set(ne),
                                  state.errors, new_err)
        loss = ctx.pmean_clients(loss_local)
        if hierarchical:
            loss = ctx.pmean_data(loss)
        # any mesh axis the loss is still typed as varying over is one the
        # round does not split it on (an unnamed size-1 axis, say): its
        # copies agree, so the mean is the value and the output replicated
        rest = tuple(jax.typeof(loss).vma)
        if rest:
            loss = lax.pmean(loss, rest)
        new_state = FedMeshState(params=new_params, m=new_st.m, v=new_st.v,
                                 vhat=new_st.vhat, errors=errors,
                                 round=new_st.t)
        # measured uplink bytes this round (trace-time constant, replicated);
        # same key/semantics as FedSim wire mode's per-round uplink metric.
        # All m client-axis devices feed the tier-1 collective — non-
        # participants contribute masked zeros that still occupy wire — so
        # that factor is m, not n_part; on the hierarchical strategy the
        # root tier adds one dense partial per GROUP (g pushes, not m: the
        # SPMD emulation replicates the partial across a group's members,
        # but the logical two-tier topology the metric bills transmits it
        # once per group — tests/test_mesh_parity.py checks both tiers
        # against the traced collective operands).
        tiers = mesh_wire_bytes_tiers(fed, delta, block=sparse_block,
                                      tp=ctx.tp)
        wire = jnp.float32(m_clients * tiers["tier1"]
                           + fed.agg_groups * tiers["tier2"])
        met = {"loss": loss, "wire_up_bytes": wire}
        if fcfg is not None:
            # replicated scalars (mask/validation are shared draws):
            # delivered survivor count and validation-rejected count —
            # the mesh siblings of FedSim's fault metrics
            met["survivors"] = jnp.sum(mask)
            met["rejected"] = rejected
        return new_state, met

    return fed_round


def mesh_metric_specs(fed: FedConfig, *, scan: bool = False):
    """PartitionSpecs for the metrics dict ``build_fed_round`` emits —
    launch sites and core.api build their shard_map ``out_specs`` through
    here so the fault-metric keys cannot drift out of sync with the round
    body. ``scan=True`` gives the stacked (R,)-leading variant."""
    sp = P(None) if scan else P()
    specs = {"loss": sp, "wire_up_bytes": sp}
    if fed.fault is not None:
        specs["survivors"] = sp
        specs["rejected"] = sp
    return specs


def jit_fed_round(model, fed: FedConfig, train: TrainConfig, mesh, *,
                  kernel_impl=None, scan: bool = False, chunk: int = 2048):
    """The jitted, shard_mapped round on ``mesh``: ``(state, batch, seed)
    -> (state, metrics)``, or with ``scan=True`` the multi-round form of
    :func:`build_fed_rounds_scan`. The state is donated, so it updates in
    place. The one builder behind ``launch.train``, ``launch.steps`` and
    ``core.api.FederatedTrainer``."""
    ctx = mesh_context(fed, mesh, train.tp_collective)
    rnd = build_fed_round(model, fed, train, ctx, chunk=chunk,
                          kernel_impl=kernel_impl)
    specs = lambda defs: jax.tree.map(lambda d: d.spec, defs,
                                      is_leaf=pdefs.is_def)
    ssp = specs(fed_state_defs(model, fed))
    bsp = specs(fed_batch_defs(model, fed, train))
    seed_spec = P()
    if scan:
        rnd, bsp, seed_spec = (build_fed_rounds_scan(rnd),
                               scan_batch_specs(bsp), P(None))
    return jax.jit(jax.shard_map(
        rnd, mesh=mesh, in_specs=(ssp, bsp, seed_spec),
        out_specs=(ssp, mesh_metric_specs(fed, scan=scan)),
        check_vma=True), donate_argnums=(0,))


def build_fed_rounds_scan(fed_round):
    """Lift a per-round mesh body to the scan-driven multi-round body:
    ``(state, batches[R], seeds[R]) -> (state, stacked metrics)``. Shared by
    core.api.FederatedTrainer and launch.train so the scan step exists in
    exactly one place (wrap in shard_map + jit with ``donate_argnums=(0,)``
    at the call site)."""

    def rounds_fn(state, batches, seeds):
        def body(st, inp):
            b, s = inp
            return fed_round(st, b, s)
        return lax.scan(body, state, (batches, seeds))

    return rounds_fn


def scan_batch_specs(batch_specs):
    """Per-round batch PartitionSpecs -> stacked (R, ...) specs."""
    return jax.tree.map(lambda s: P(None, *tuple(s)), batch_specs)


def stage_mesh_rounds(lm_data, r0: int, count: int, local_steps: int,
                      global_batch: int, seq_len: int):
    """Host-side staging for ``count`` mesh rounds: stacked (R, ...) batch
    dict + (R,) int32 seeds for :func:`build_fed_rounds_scan` (shared by
    core.api and launch.train)."""
    raws = [lm_data.mesh_batch(r, local_steps, global_batch, seq_len)
            for r in range(r0, r0 + count)]
    batch = {k: jnp.asarray(np.stack([b[k] for b in raws]))
             for k in raws[0]}
    return batch, jnp.arange(r0, r0 + count, dtype=jnp.int32)


def fed_batch_defs(model, fed: FedConfig, train: TrainConfig):
    """GLOBAL batch defs with client-axis sharding, leading K dim."""
    b = model.train_batch_defs(train.global_batch, train.seq_len)
    axes = client_batch_axes(fed)
    ax = axes[0] if len(axes) == 1 else tuple(axes)

    def stack_k(d: pdefs.ParamDef):
        import dataclasses
        spec = list(d.spec)
        spec[0] = ax  # batch dim over client (+data) axes
        return dataclasses.replace(
            d, shape=(fed.local_steps,) + tuple(d.shape), spec=P(None, *spec))

    return jax.tree.map(stack_k, b, is_leaf=pdefs.is_def)
