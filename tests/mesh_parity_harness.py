"""Subprocess side of the sim-vs-mesh differential parity harness.

Runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` via
``tests/conftest.py::run_forced_devices`` (imported by
``tests/test_mesh_parity.py``; never collected by pytest). One process
executes EVERY grid case so both backends of every pair share one jax
init and one XLA codegen (bit-parity claims are same-process claims —
see CHANGES.md PR 3 note).

The differential fixture is a single-leaf model whose local phase has no
floating-point reassociation freedom at all:

    loss(w, batch) = 0.5 · Σ_b Σ_j (w_j − t_{b,j})²   with B = 2 rows

so the gradient is the two-term sum ``(w − t_0) + (w − t_1)`` — float
addition is commutative (only associativity is order-sensitive), so the
vmapped FedSim local phase and the per-device mesh local phase produce
bit-identical deltas (a host-side replay would NOT: XLA fuses
``w − η·g`` into an FMA inside the jitted rounds, so only same-program /
same-fusion pairs are bitwise comparable). Everything downstream — EF
totals, the selection, packed-sign hats, the compacted-Selection
collective, the server update — is then compared at the bit level.
``errors`` equality IS the per-round selection equality: the EF residual
is ``tot`` with exactly the selected coordinates zeroed, so two backends
with bit-equal state that selected differently would disagree on the EF
rows wherever ``tot ≠ 0``. That each backend's selection equals the
*reference* compressor is the already-established other half of the
chain: tests/test_sparse_uplink.py (sim select-once ≡ dense reference),
tests/test_kernels.py (Pallas kernel ≡ ``Compressor.select``), and the
single-device stage properties in tests/test_mesh_parity.py.

Targets are quantized to a 0.25 grid so |tot| ties actually occur and the
``lax.top_k`` lowest-index tie-breaking is exercised end-to-end (both
backends must break them identically for the bitwise comparison to hold).
"""
from __future__ import annotations

import json

import numpy as np

D = 2176        # block_layout → 2 blocks of 2048, 1920-element padded tail
M = 8           # clients == forced host devices
BC = 2          # per-client batch rows per local step (2-term reduce only)
K = 2           # local steps
R = 3           # rounds per case
ETA, ETA_L = 0.25, 0.0625
RATIO = 1.0 / 8.0

# name -> mesh-side FedConfig kwargs. The sim side mirrors the mesh's
# documented topk -> blocktopk remap (core/mesh.py: per-leaf global top-k
# is ill-defined on sharded leaves) and ignores aggregation /
# mesh_sparse_impl; `wire` applies to the sim side only (the mesh's wire
# IS the collective) — at float32 wire value dtype the sim wire path is
# bit-exact, so the same mesh run must match both.
CASES = {
    "dense": dict(algorithm="fedams", compressor="none",
                  aggregation="dense"),
    "topk": dict(algorithm="fedcams", compressor="topk",
                 aggregation="sparse"),
    "blocktopk": dict(algorithm="fedcams", compressor="blocktopk",
                      aggregation="sparse"),
    "packedsign": dict(algorithm="fedcams", compressor="packedsign",
                       aggregation="sparse"),
    # the kernel-routed tentpole path: same grid point as "blocktopk" but
    # the Selection comes out of the fused Pallas topk_ef_sparse kernel
    # (interpret mode on CPU — bit-identical to the compiled TPU kernel)
    "blocktopk_kernel": dict(algorithm="fedcams", compressor="blocktopk",
                             aggregation="sparse",
                             mesh_sparse_impl="kernel"),
    # one-pass fused server ingest (DESIGN.md §3): the gathered (vals, idx)
    # go straight into the m/v/v̂/x update with no dense mean delta. Both
    # sides run the SAME provider (jnp blocked scatter / Pallas
    # fedams_ingest), so the pair stays bitwise comparable; track_gamma off
    # because the γ diagnostic consumes the dense aggregate the fused path
    # never builds.
    "blocktopk_fused": dict(algorithm="fedcams", compressor="blocktopk",
                            aggregation="sparse", fused_ingest="jnp",
                            track_gamma=False),
    "blocktopk_fused_kernel": dict(algorithm="fedcams",
                                   compressor="blocktopk",
                                   aggregation="sparse",
                                   fused_ingest="kernel",
                                   track_gamma=False),
    # two-level hierarchical aggregation (DESIGN.md §scale-out): the 8
    # clients split into 4 edge groups of 2 on a (cgroup=4, data=2) mesh;
    # tier 1 merges each group's selections into a dense partial, tier 2
    # gathers the 4 partials at the root. The sim side runs the same
    # grouping through server_aggregate_sparse_grouped — both sides reduce
    # the group partials with an identical jnp.sum over a stacked (g, d)
    # array, so the pair stays bitwise comparable.
    "blocktopk_hier": dict(algorithm="fedcams", compressor="blocktopk",
                           aggregation="sparse", agg_groups=4),
    "blocktopk_hier_kernel": dict(algorithm="fedcams",
                                  compressor="blocktopk",
                                  aggregation="sparse", agg_groups=4,
                                  mesh_sparse_impl="kernel"),
}


def _round_targets(r: int):
    """(K, GB, D) quantized targets for round ``r`` — the mesh batch.
    Client i owns rows [i·BC, (i+1)·BC) of the GB axis (the "data"-axis
    shard order), every local step."""
    rng = np.random.default_rng(1000 + r)
    t = rng.normal(size=(K, M * BC, D)).astype(np.float32)
    return np.round(t * 4.0) / 4.0


def _sim_batches(t):
    """Mesh (K, GB, D) -> sim (M, K, BC, D), client-major."""
    return t.reshape(K, M, BC, D).transpose(1, 0, 2, 3)


class ParityModel:
    """Single-leaf deterministic model (see module docstring)."""

    def __init__(self, d: int = D):
        self.d = d

    def defs(self):
        from jax.sharding import PartitionSpec as P

        from repro.models import params as pdefs
        return {"w": pdefs.ParamDef((self.d,), P(), dtype="float32")}

    def loss(self, p, b, ctx, remat_policy="none", chunk=0):
        import jax.numpy as jnp
        diff = p["w"][None, :] - b["t"]
        return 0.5 * jnp.sum(diff * diff), ()

    def train_batch_defs(self, global_batch, seq_len):
        from jax.sharding import PartitionSpec as P

        from repro.models import params as pdefs
        return {"t": pdefs.ParamDef((global_batch, self.d), P(None, None),
                                    dtype="float32")}


def _run_mesh(fed, rounds_targets, kernel_impl):
    import jax
    import jax.numpy as jnp

    from repro.configs.base import TrainConfig
    from repro.core.mesh import (build_fed_round, fed_batch_defs,
                                 fed_state_defs, init_fed_state)
    from repro.launch.mesh import make_mesh
    from repro.models import params as pdefs
    from repro.sharding.rules import ParallelContext
    from jax.sharding import PartitionSpec as P

    model = ParityModel()
    train = TrainConfig(global_batch=M * BC, seq_len=1, remat_policy="none")
    if fed.agg_groups > 1:
        # hierarchical layout: first client axis is the group axis; device
        # linear order stays client-major, so target slicing is unchanged
        mesh = make_mesh((fed.agg_groups, M // fed.agg_groups),
                         ("cgroup", "data"))
    else:
        mesh = make_mesh((M,), ("data",))
    ctx = ParallelContext(client_axes=fed.client_axes, num_clients=M)
    sdefs = fed_state_defs(model, fed)
    ssp = jax.tree.map(lambda d: d.spec, sdefs, is_leaf=pdefs.is_def)
    bsp = jax.tree.map(lambda d: d.spec, fed_batch_defs(model, fed, train),
                       is_leaf=pdefs.is_def)
    rnd = jax.jit(jax.shard_map(
        build_fed_round(model, fed, train, ctx, kernel_impl=kernel_impl),
        mesh=mesh, in_specs=(ssp, bsp, P()),
        out_specs=(ssp, {"loss": P(), "wire_up_bytes": P()})))
    state = init_fed_state(model, fed, jax.random.PRNGKey(0))
    out = []
    for r, t in enumerate(rounds_targets):
        state, met = rnd(state, {"t": jnp.asarray(t)}, jnp.int32(r))
        out.append(dict(
            params=np.asarray(state.params["w"]),
            errors=np.asarray(state.errors["w"]),
            loss=float(met["loss"]),
            wire_up_bytes=float(met["wire_up_bytes"])))
    return out


def _run_sim(fed, rounds_targets):
    import jax
    import jax.numpy as jnp

    from repro.core.sim import FedSim
    from repro.models import params as pdefs

    model = ParityModel()
    sim = FedSim(lambda p, b: model.loss(p, b, None), fed)
    st = sim.init(pdefs.init_params(model.defs(), jax.random.PRNGKey(0)))
    out = []
    for r, t in enumerate(rounds_targets):
        st, met = sim.round(st, {"t": jnp.asarray(_sim_batches(t))},
                            jnp.arange(M, dtype=jnp.int32),
                            jax.random.PRNGKey(100 + r))
        out.append(dict(
            params=np.asarray(st.params["w"]),
            errors=np.asarray(st.errors),
            loss=float(met["loss"])))
    return out


def _select_only_kernel_impl():
    """A KernelImpl that serves ONLY the sparse-uplink selection: the
    server update stays on the shared jnp ``server_update`` (passing a
    full KernelImpl also swaps in the Pallas FedAMS server kernel — same
    update math, but XLA may compile its x division with a different
    FMA/rsqrt contraction than the sim's differently-shaped program, a
    few ulp that tests/test_server_opt.py owns, not this uplink
    harness)."""
    from repro.core.server_opt import server_update
    from repro.kernels.ops import KernelImpl

    class SelectOnlyKernelImpl(KernelImpl):
        def fedams_update_tree(self, fed, st, params, agg):
            return server_update(fed, st, params, agg)

    return SelectOnlyKernelImpl()


def run_case(name: str, wire: bool) -> list:
    """One paired run -> per-round tree-compare summary dicts."""
    from repro.configs.base import FedConfig

    kw = dict(CASES[name])
    mesh_impl = kw.pop("mesh_sparse_impl", "auto")
    groups = kw.get("agg_groups", 1)
    common = dict(compress_ratio=RATIO, local_steps=K, num_clients=M,
                  eta=ETA, eta_l=ETA_L)
    mesh_axes = ("cgroup", "data") if groups > 1 else ("data",)
    fed_mesh = FedConfig(client_axes=mesh_axes, mesh_sparse_impl=mesh_impl,
                         **kw, **common)
    sim_kw = dict(kw)
    if sim_kw["compressor"] == "topk":     # mirror the mesh's documented remap
        sim_kw["compressor"] = "blocktopk"
    fed_sim = FedConfig(client_axes=(), wire=wire, **sim_kw, **common)

    targets = [_round_targets(r) for r in range(R)]
    # fused_ingest="kernel" needs the FULL KernelImpl on the mesh side (the
    # ingest kernel replaces the server update entirely, so the
    # select-only shim's jnp fallback never runs); the select-only shim
    # serves the mesh_sparse_impl="kernel" case, where only the selection
    # should come from Pallas.
    if kw.get("fused_ingest") == "kernel":
        from repro.kernels.ops import KernelImpl
        ki = KernelImpl()
    else:
        ki = _select_only_kernel_impl() if mesh_impl == "kernel" else None
    mesh_rounds = _run_mesh(fed_mesh, targets, ki)
    sim_rounds = _run_sim(fed_sim, targets)

    rows = []
    for r, (mr, sr) in enumerate(zip(mesh_rounds, sim_rounds)):
        scale = float(max(np.abs(sr["params"]).max(), 1e-30))
        rows.append({
            "round": r,
            "errors_bitwise": bool((mr["errors"] == sr["errors"]).all()),
            "errors_maxdiff": float(
                np.abs(mr["errors"] - sr["errors"]).max()),
            "params_bitwise": bool((mr["params"] == sr["params"]).all()),
            # params inherit exactly the aggregate's difference through the
            # elementwise server update -> report it in ulp-like units
            "params_maxdiff_rel": float(
                np.abs(mr["params"] - sr["params"]).max() / scale),
            "loss_mesh": mr["loss"], "loss_sim": sr["loss"],
            "wire_up_bytes": mr["wire_up_bytes"],
        })
    return rows


def jaxpr_payload(compressor: str) -> dict:
    """Trace (never execute) the sparse mesh round for a TWO-leaf model and
    measure what the client-axis all_gathers actually carry, plus how many
    selections run. Returns per-trace totals and the `mesh_wire_bytes`
    metric for the same config, so the test can assert metric == measured.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import FedConfig, TrainConfig
    from repro.core.mesh import (build_fed_round, fed_batch_defs,
                                 fed_state_defs, init_fed_state,
                                 mesh_wire_bytes)
    from repro.launch.mesh import make_mesh
    from repro.models import params as pdefs
    from repro.sharding.rules import ParallelContext

    class TwoLeafModel(ParityModel):
        # second leaf (300 elements: one 384-wide padded block) rides along
        # so per-leaf counts are distinguishable from per-tree counts
        def defs(self):
            base = super().defs()
            base["b"] = pdefs.ParamDef((300,), P(), dtype="float32")
            return base

        def loss(self, p, b, ctx, remat_policy="none", chunk=0):
            diff = p["w"][None, :] - b["t"]
            return (0.5 * jnp.sum(diff * diff)
                    + 0.5 * jnp.sum(p["b"] * p["b"]), ())

    fed = FedConfig(algorithm="fedcams", compressor=compressor,
                    aggregation="sparse", compress_ratio=RATIO,
                    local_steps=K, num_clients=M, eta=ETA, eta_l=ETA_L,
                    client_axes=("data",))
    model = TwoLeafModel()
    train = TrainConfig(global_batch=M * BC, seq_len=1, remat_policy="none")
    mesh = make_mesh((M,), ("data",))
    ctx = ParallelContext(client_axes=("data",), num_clients=M)
    sdefs = fed_state_defs(model, fed)
    ssp = jax.tree.map(lambda d: d.spec, sdefs, is_leaf=pdefs.is_def)
    bsp = jax.tree.map(lambda d: d.spec, fed_batch_defs(model, fed, train),
                       is_leaf=pdefs.is_def)
    fn = jax.shard_map(build_fed_round(model, fed, train, ctx),
                          mesh=mesh, in_specs=(ssp, bsp, P()),
                          out_specs=(ssp, {"loss": P(),
                                           "wire_up_bytes": P()}))
    state = init_fed_state(model, fed, jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(fn)(
        state, {"t": jnp.zeros((K, M * BC, D), jnp.float32)}, jnp.int32(0))

    gathered = []      # (bytes, shape) per all_gather operand
    counts = {"top_k": 0, "argmax": 0}

    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subjaxprs(params):
        for v in params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for s in vs:
                if isinstance(s, ClosedJaxpr):
                    yield s.jaxpr
                elif isinstance(s, Jaxpr):
                    yield s

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in ("all_gather", "all_gather_invariant"):
                v = eqn.invars[0].aval
                gathered.append([int(np.prod(v.shape)) * v.dtype.itemsize,
                                 list(v.shape)])
            if eqn.primitive.name in counts:
                counts[eqn.primitive.name] += 1
            for s in subjaxprs(eqn.params):
                walk(s)

    walk(jaxpr.jaxpr)

    delta_tree = {"w": np.zeros(D, np.float32), "b": np.zeros(300, np.float32)}
    return {
        "gathered": gathered,
        "gathered_bytes": int(sum(g[0] for g in gathered)),
        "top_k": counts["top_k"], "argmax": counts["argmax"],
        "metric_bytes": int(mesh_wire_bytes(fed, delta_tree, tp=1)),
        "dense_bytes": 4 * (D + 300),
        "num_leaves": 2,
    }


def jaxpr_payload_hier() -> dict:
    """Trace the HIERARCHICAL sparse round (g = 2 groups of 4 on the forced
    8-device mesh) at ratio 1/2 and split the client-axis all_gathers by
    tier: "data"-axis gathers carry the member selections (tier 1),
    "cgroup"-axis gathers carry the dense group partials the root consumes
    (tier 2). At this ratio the root payload win is provable in-process:
    the root sees g dense fp32 partials (g·d·4 bytes) instead of n
    selections (n·k·8 = n·d/2·8 = 4·n·d bytes) — the O(g·d) vs O(n·k)
    crossover the metric bills (``mesh_wire_bytes_tiers``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import FedConfig, TrainConfig
    from repro.core.mesh import (build_fed_round, fed_batch_defs,
                                 fed_state_defs, init_fed_state,
                                 mesh_wire_bytes_tiers)
    from repro.launch.mesh import make_mesh
    from repro.models import params as pdefs
    from repro.sharding.rules import ParallelContext

    class TwoLeafModel(ParityModel):
        def defs(self):
            base = super().defs()
            base["b"] = pdefs.ParamDef((300,), P(), dtype="float32")
            return base

        def loss(self, p, b, ctx, remat_policy="none", chunk=0):
            diff = p["w"][None, :] - b["t"]
            return (0.5 * jnp.sum(diff * diff)
                    + 0.5 * jnp.sum(p["b"] * p["b"]), ())

    g = 2
    ratio = 0.5    # dense-partial tier wins only for k large: s > 1/ratio
    fed = FedConfig(algorithm="fedcams", compressor="blocktopk",
                    aggregation="sparse", compress_ratio=ratio,
                    agg_groups=g, local_steps=K, num_clients=M,
                    eta=ETA, eta_l=ETA_L, client_axes=("cgroup", "data"))
    model = TwoLeafModel()
    train = TrainConfig(global_batch=M * BC, seq_len=1, remat_policy="none")
    mesh = make_mesh((g, M // g), ("cgroup", "data"))
    ctx = ParallelContext(client_axes=("cgroup", "data"), num_clients=M)
    sdefs = fed_state_defs(model, fed)
    ssp = jax.tree.map(lambda d: d.spec, sdefs, is_leaf=pdefs.is_def)
    bsp = jax.tree.map(lambda d: d.spec, fed_batch_defs(model, fed, train),
                       is_leaf=pdefs.is_def)
    fn = jax.shard_map(build_fed_round(model, fed, train, ctx),
                          mesh=mesh, in_specs=(ssp, bsp, P()),
                          out_specs=(ssp, {"loss": P(),
                                           "wire_up_bytes": P()}))
    state = init_fed_state(model, fed, jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(fn)(
        state, {"t": jnp.zeros((K, M * BC, D), jnp.float32)}, jnp.int32(0))

    tiers = {"tier1": [], "tier2": []}   # (operand bytes, shape) per gather

    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subjaxprs(params):
        for v in params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for s in vs:
                if isinstance(s, ClosedJaxpr):
                    yield s.jaxpr
                elif isinstance(s, Jaxpr):
                    yield s

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in ("all_gather", "all_gather_invariant"):
                ax = eqn.params.get("axis_name", ())
                axes = ax if isinstance(ax, tuple) else (ax,)
                v = eqn.invars[0].aval
                rec = [int(np.prod(v.shape)) * v.dtype.itemsize,
                       list(v.shape)]
                # one gather per axis (rules._gather_axes loops), so each
                # eqn belongs to exactly one tier
                tiers["tier2" if "cgroup" in axes else "tier1"].append(rec)
            for s in subjaxprs(eqn.params):
                walk(s)

    walk(jaxpr.jaxpr)

    delta_tree = {"w": np.zeros(D, np.float32),
                  "b": np.zeros(300, np.float32)}
    metric = mesh_wire_bytes_tiers(fed, delta_tree, tp=1)
    # what the FLAT root would carry at the same ratio: every client's
    # compacted selection (vals f32 + idx i32), summed over leaves
    fed_flat = FedConfig(algorithm="fedcams", compressor="blocktopk",
                         aggregation="sparse", compress_ratio=ratio,
                         local_steps=K, num_clients=M, eta=ETA, eta_l=ETA_L,
                         client_axes=("data",))
    flat_tiers = mesh_wire_bytes_tiers(fed_flat, delta_tree, tp=1)
    return {
        "tier1_gathers": tiers["tier1"],
        "tier2_gathers": tiers["tier2"],
        "tier1_operand_bytes": int(sum(t[0] for t in tiers["tier1"])),
        "tier2_operand_bytes": int(sum(t[0] for t in tiers["tier2"])),
        "metric_tier1_bytes": int(metric["tier1"]),
        "metric_tier2_bytes": int(metric["tier2"]),
        "agg_groups": g,
        "num_clients": M,
        "root_bytes_hier": g * int(metric["tier2"]),
        "root_bytes_flat": M * int(flat_tiers["tier1"]),
        "num_leaves": 2,
    }


def main() -> None:
    out = {"cases": {}, "jaxpr": {}}
    for name in CASES:
        for wire in (False, True):
            out["cases"][f"{name}_wire{int(wire)}"] = run_case(name, wire)
    for compressor in ("blocktopk", "packedsign"):
        out["jaxpr"][compressor] = jaxpr_payload(compressor)
    out["jaxpr_hier"] = jaxpr_payload_hier()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
