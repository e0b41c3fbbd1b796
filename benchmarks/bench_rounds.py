"""FedSim round throughput: pre-PR-style per-round driver vs the scan driver.

Three execution paths over identical inputs:

``legacy``
    The pre-PR harness, reconstructed: per-round batch staging
    (``round_batches`` + ``jnp.asarray`` every round), a non-donated jit of
    the round body, and a device→host metrics sync every round — what the
    fig1–fig7 benchmarks paid per round before this PR. (The pre-PR code
    also kept bits/round counters on device and synced ``int(state.round)``
    for transport; those are omitted here, which *favors* legacy.)
``loop``
    The post-PR per-round path: donated state, host-side counters,
    pre-staged inputs — one jitted dispatch + one metrics sync per round.
``scan``
    ``FedSim.run_rounds``: R rounds in one ``lax.scan`` dispatch with
    donated carry and a single host sync. Bit-identical to ``loop``.

Measured on two configs:

* ``bench_wire_e2e`` — the bench_wire end-to-end config (m=50, n=10, K=3,
  topk r=1/64, wire=True). On accelerator-class hosts the round body is
  dispatch-bound and the scan driver dominates; on small CPU containers the
  MLP's local-training matmuls are genuine compute (measured ~20 GFLOP/s on
  the batched per-client matmuls), which bounds the achievable
  scan-vs-loop ratio once the driver overhead is gone.
* ``overhead_bound`` — same m/n/wire with K=1 and a smaller model, the
  regime the ISSUE's motivation describes (driver overhead >> round math),
  where the scan driver's speedup is expected to clear 5×.

A third dimension sweeps the local-update rule (core/local.py): scan-driver
throughput for sgd / sgdm / prox plus the eta_l_decay and heterogeneous-K
scenario knobs on the overhead-bound config — the sgd number doubles as the
regression gate for the rounds-monolith → layered-engine split (the split
must cost no scan-driver throughput).

A fourth dimension measures the select-once sparse uplink (DESIGN.md §3)
on ``compression_bound``: d ≈ 1.15e6 (pad-free: 560 blocks of 2048),
blockwise top-1 (ratio 1/2048), K=1, batch=1, wire on, γ diagnostic off —
the regime where the round is dominated by the uplink's O(n·d) memory
passes rather than local training. Dense (``sparse_uplink=False``, the
reference pipeline: per-client encode→bytes→dense-scatter decode→(n, d)
hat block→dense mean→dense EF rebuild) is A/B'd against the sparse path
(one selection per client — an argmax reduce at k'=1, never a sort-based
``lax.top_k``; the (vals, idx) pair flows to an O(n·k + d) server scatter;
EF touches only selected coordinates), both end-to-end and as the isolated
uplink+aggregate stage.

A fifth dimension (``server_ingest``) gates the one-pass fused server
ingest (DESIGN.md §3): at the same compression-bound scale it compiles
the staged two-pass server step (``server_aggregate_sparse`` jit +
``server_update`` jit — the dense mean delta is materialized between
them) against the fused ``server_ingest`` jit per
``server_state_dtype`` and reports bytes-moved-per-round two ways:
an analytic stream model (materialized f32-equivalent output streams
over the d-sized domain) and the HLO measurement from
``launch.hlo_analysis`` (``bytes`` = materialized-buffer traffic, the
repo's §Perf convention and the gate metric; ``rw_bytes`` = the
read+write estimate, reported as a diagnostic). The fused path never
materializes the dense (d,) mean delta, so its ``bytes`` sits strictly
below the two-pass number at every state dtype; quantized v/v̂ storage
(bf16, int8) shrinks it further. CPU caveat for the ``rw_bytes``
diagnostic: XLA CPU recomputes fused elementwise moments inside every
consumer fusion instead of re-reading them, so at fp32 the fused
read+write estimate roughly ties the two-pass one — the win there is
the removed materialization, which is exactly what ``bytes`` counts.

Container caveat (mirrors PR-2's 5x note): the ISSUE's ≥3x target for
sparse-vs-dense presumes an accelerator-class host where the dense path's
(n, d) hat block + mean is HBM-traffic-bound and the compacted
(vals, idx) block (kernels.topk_ef_sparse emits it in a single HBM pass)
removes that traffic. On this 2-vCPU CPU container XLA fuses the dense
path's extra passes into the same bandwidth-bound streams the round
already pays (EF gather/update, local training), and CPU scatter costs
~100 ns/update, so the measured end-to-end win is ~1.2x at k'=1 (parity
at ratio 1/64, where the shared sort-based top-k dominates both paths)
with ~1.4x on the overhead-bound config. The CI gate asserts the sparse
path never regresses below the dense one on this config.

Writes everything to ``BENCH_rounds.json`` at the repo root (via
benchmarks.common) so the perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import QUICK, csv_row, update_bench_json

from repro.configs.base import FedConfig
from repro.core.rounds import FedSim, _CoreState
from repro.core.sampling import sample_clients
from repro.data.synthetic import FederatedClassification
from repro.models import params as pdefs
from repro.models.convmixer import MLPConfig, mlp_defs, mlp_loss

E2E = dict(name="bench_wire_e2e",
           mlp=dict(in_dim=32, hidden=64, depth=2, num_classes=10),
           local_steps=3, batch=20)
OVERHEAD = dict(name="overhead_bound",
                mlp=dict(in_dim=16, hidden=16, depth=1, num_classes=4),
                local_steps=1, batch=8, eta=0.03, eta_l=0.03)
FED_KW = dict(algorithm="fedcams", num_clients=50, participating=10,
              compressor="topk", compress_ratio=1 / 64, eta=0.1, eta_l=0.05,
              wire=True)
# d = 1058² + (16+2+8)·1058 + 8 = 1,146,880 = 560 · 2048 exactly (no padded
# tail anywhere in the pipeline); blockwise top-1 is the compression-bound
# point: selection is a reduce and the message is 560 (val, idx) pairs.
COMPRESSION = dict(name="compression_bound",
                   mlp=dict(in_dim=16, hidden=1058, depth=2, num_classes=8),
                   local_steps=1, batch=1)
COMPRESSION_FED_KW = dict(algorithm="fedcams", num_clients=10,
                          participating=10, compressor="blocktopk",
                          compress_ratio=1 / 2048, wire_block=2048,
                          eta=0.1, eta_l=0.05, wire=True, track_gamma=False)
# Sixth dimension (``scale_out``, DESIGN.md §scale-out): the EF shard
# store at m = 10^4 .. 10^6 clients with a FIXED participating cohort.
# d = 16·64+64 + 64·64+64 + 64·8+8 = 5768; the resident (m, d) EF buffer
# is 231 MB at m=10^4 and 23 GB at m=10^6 — the sharded path must hold
# device residency flat (cohort-sized) across the whole sweep. wire=True
# so the sweep also exercises the lazy per-client link draws.
SCALE = dict(name="scale_out",
             mlp=dict(in_dim=16, hidden=64, depth=2, num_classes=8),
             local_steps=2, batch=8)
SCALE_FED_KW = dict(algorithm="fedcams", compressor="blocktopk",
                    compress_ratio=1 / 64, participating=32, eta=0.1,
                    eta_l=0.05, wire=True, track_gamma=False)


def _make_sim(cfg):
    mc = MLPConfig(**cfg["mlp"])
    kw = dict(FED_KW, **{k: cfg[k] for k in ("eta", "eta_l") if k in cfg})
    fed = FedConfig(local_steps=cfg["local_steps"], **kw)
    sim = FedSim(lambda p, b: mlp_loss(p, b, mc), fed)
    st = sim.init(pdefs.init_params(mlp_defs(mc), jax.random.PRNGKey(0)))
    return sim, st


def _fresh_state(sim, cfg):
    mc = MLPConfig(**cfg["mlp"])
    sim.network = type(sim.network)(sim.network.cfg, FED_KW["num_clients"])
    sim.comm_log = type(sim.comm_log)()
    return sim.init(pdefs.init_params(mlp_defs(mc), jax.random.PRNGKey(0)))


def _stage(data, cfg, rounds: int):
    """Identical staged inputs for every path: (R, n, K, ...) batches,
    (R, n) indices, (R,) keys — plus the host-side per-round views the
    legacy path re-stages from."""
    rng = jax.random.PRNGKey(1)
    idxs, keys, batches = [], [], []
    for r in range(rounds):
        rng, k1, k2 = jax.random.split(rng, 3)
        idx = np.asarray(sample_clients(k1, FED_KW["num_clients"],
                                        FED_KW["participating"]))
        batches.append(data.round_batches(idx, r, cfg["local_steps"],
                                          cfg["batch"]))
        idxs.append(idx)
        keys.append(k2)
    stacked = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *batches)
    return stacked, jnp.asarray(np.stack(idxs)), jnp.stack(keys), np.stack(idxs)


def _run_legacy(sim, fn, st, data, cfg, idx_host, keys, rounds: int):
    """Pre-PR driver semantics: stage + dispatch + sync every round.
    ``fn`` is the shared non-donated jit of the round body (like the seed's
    round jit; hoisted so compile stays out of the timing)."""
    bits = 0
    for r in range(rounds):
        raw = data.round_batches(idx_host[r], r, cfg["local_steps"],
                                 cfg["batch"])
        b = jax.tree.map(jnp.asarray, raw)
        core, met = fn(_CoreState(*st[:5]), b, jnp.asarray(idx_host[r]),
                       keys[r], jnp.int32(r))
        bits += sim._bits_per_round(idx_host.shape[1])
        met = dict(met)
        met["bits"] = bits
        met.update(sim._record_timing(sim._round_timing(idx_host[r], r),
                                      None))
        met = {k: float(v) for k, v in met.items()}  # per-round sync
        st = type(st)(*core, bits=bits, round=r + 1)
    return st, met


def _run_loop(sim, st, batches, idx, keys, rounds: int):
    """Post-PR per-round path, consumed the way FederatedTrainer consumes
    it (per-round float() conversion = one device sync per round)."""
    last = None
    for r in range(rounds):
        b_r = jax.tree.map(lambda x: x[r], batches)
        st, met = sim.round(st, b_r, idx[r], keys[r])
        last = {k: float(v) for k, v in met.items()}
    return st, last


def _run_scan(sim, st, batches, idx, keys):
    st, mets = sim.run_rounds(st, batches, idx, keys)
    return st, {k: float(v) for k, v in mets[-1].items()}


def measure(cfg, rounds: int) -> dict:
    data = FederatedClassification(num_clients=FED_KW["num_clients"],
                                   num_classes=cfg["mlp"]["num_classes"],
                                   feature_dim=cfg["mlp"]["in_dim"], seed=0)
    batches, idx, keys, idx_host = _stage(data, cfg, rounds)

    sim, st = _make_sim(cfg)
    legacy_fn = jax.jit(sim._round_impl)  # NOT donated, like the seed jit
    _run_legacy(sim, legacy_fn, st, data, cfg, idx_host, keys, 2)  # warmup
    st = _fresh_state(sim, cfg)
    t0 = time.perf_counter()
    st_l, met_legacy = _run_legacy(sim, legacy_fn, st, data, cfg, idx_host,
                                   keys, rounds)
    jax.block_until_ready(st_l.params)
    t_legacy = time.perf_counter() - t0

    sim2, st2 = _make_sim(cfg)
    _run_loop(sim2, st2, batches, idx, keys, 2)  # warmup
    st2 = _fresh_state(sim2, cfg)
    t0 = time.perf_counter()
    st_loop, met_loop = _run_loop(sim2, st2, batches, idx, keys, rounds)
    jax.block_until_ready(st_loop.params)
    t_loop = time.perf_counter() - t0

    sim3, st3 = _make_sim(cfg)
    _run_scan(sim3, st3, batches, idx, keys)  # warmup
    st3 = _fresh_state(sim3, cfg)
    t0 = time.perf_counter()
    st_scan, met_scan = _run_scan(sim3, st3, batches, idx, keys)
    jax.block_until_ready(st_scan.params)
    t_scan = time.perf_counter() - t0

    # loop and scan consume identical staged inputs -> identical results
    assert met_loop["wire_bytes"] == met_scan["wire_bytes"]
    assert np.array_equal(met_loop["loss"], met_scan["loss"],
                          equal_nan=True), (met_loop, met_scan)
    wire_bytes = met_scan["wire_bytes"]
    return {
        "config": dict(FED_KW, rounds=rounds, d=int(sim._d), **{
            k: v for k, v in cfg.items() if k != "name"}),
        "legacy_rounds_per_s": rounds / t_legacy,
        "loop_rounds_per_s": rounds / t_loop,
        "scan_rounds_per_s": rounds / t_scan,
        "speedup_scan_vs_legacy": t_legacy / t_scan,
        "speedup_scan_vs_loop": t_loop / t_scan,
        "wire_bytes_total": int(wire_bytes),
        "scan_wire_bytes_per_s": wire_bytes / t_scan,
        "final_loss": met_scan["loss"],
    }


def _measure_sparse_ab(cfg, fed_kw, rounds: int, reps: int) -> dict:
    """Scan-driver rounds/s for sparse_uplink True vs False on identical
    staged inputs (median of ``reps`` runs — this container is noisy)."""
    data = FederatedClassification(num_clients=fed_kw["num_clients"],
                                   num_classes=cfg["mlp"]["num_classes"],
                                   feature_dim=cfg["mlp"]["in_dim"], seed=0)
    rng = jax.random.PRNGKey(1)
    idxs, keys, batches = [], [], []
    for r in range(rounds):
        rng, k1, k2 = jax.random.split(rng, 3)
        idx = np.asarray(sample_clients(k1, fed_kw["num_clients"],
                                        fed_kw["participating"]))
        batches.append(data.round_batches(idx, r, cfg["local_steps"],
                                          cfg["batch"]))
        idxs.append(idx)
        keys.append(k2)
    stacked = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *batches)
    idx, keys = jnp.asarray(np.stack(idxs)), jnp.stack(keys)

    mc = MLPConfig(**cfg["mlp"])
    sims, ts = {}, {"dense": [], "sparse": []}
    out = {}
    for sparse in (False, True):
        fed = FedConfig(local_steps=cfg["local_steps"],
                        sparse_uplink=sparse, **fed_kw)
        sims["sparse" if sparse else "dense"] = FedSim(
            lambda p, b: mlp_loss(p, b, mc), fed)
    # interleaved A/B, fastest-of-N per path: this container's run-to-run
    # noise (±30-40%) would otherwise dominate the measured ratio
    for rep in range(reps + 1):         # first pair compiles
        for key, sim in sims.items():
            if sim.network is not None:
                sim.network = type(sim.network)(sim.network.cfg,
                                                fed_kw["num_clients"])
                sim.comm_log = type(sim.comm_log)()
            st = sim.init(pdefs.init_params(mlp_defs(mc),
                                            jax.random.PRNGKey(0)))
            t0 = time.perf_counter()
            st, mets = sim.run_rounds(st, stacked, idx, keys)
            jax.block_until_ready(st.params)
            ts[key].append(time.perf_counter() - t0)
            out[f"{key}_final_loss"] = float(mets[-1]["loss"])
    for key in sims:
        out[f"{key}_rounds_per_s"] = rounds / float(np.min(ts[key][1:]))
    out["speedup_sparse_vs_dense"] = (out["sparse_rounds_per_s"]
                                      / out["dense_rounds_per_s"])
    return out


def _measure_uplink_stage(d: int, n: int, fed_kw, rounds: int,
                          reps: int) -> dict:
    """The tentpole in isolation: EF→select/compress→aggregate over staged
    (rounds, n, d) deltas, scanned — no local training, no server update.
    Uses the same stage functions the round composes (core/stages.py)."""
    import functools

    from repro.comm import make_wire_codec
    from repro.core.compressors import make_compressor
    from repro.core.stages import (client_uplink, client_uplink_sparse,
                                   ef_update_sparse, server_aggregate_sparse)

    comp = make_compressor(fed_kw["compressor"], fed_kw["compress_ratio"],
                           fed_kw["wire_block"])
    codec = make_wire_codec(fed_kw["compressor"], fed_kw["compress_ratio"],
                            fed_kw["wire_block"])
    m = fed_kw["num_clients"]
    rng = jax.random.PRNGKey(0)
    deltas = 0.01 * jax.random.normal(rng, (rounds, n, d), jnp.float32)
    cidx = jnp.stack([jax.random.permutation(jax.random.fold_in(rng, r),
                                             m)[:n] for r in range(rounds)])
    pos = jnp.arange(n)

    def dense_body(errors, inp):
        delta, ci = inp
        errs = errors[ci]
        hats, nerrs = client_uplink(comp, codec, d, rng, delta, errs, pos)
        return errors.at[ci].set(nerrs), jnp.mean(hats, axis=0)

    def sparse_body(errors, inp):
        delta, ci = inp
        errors = errors.at[ci].add(delta)
        vals, sidx, rxv = client_uplink_sparse(comp, codec, d, rng,
                                               errors[ci], pos)
        errors = ef_update_sparse(errors, ci, sidx, vals, rxv)
        return errors, server_aggregate_sparse(rxv, sidx, d, n)

    def make_fn(body):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def scan_fn(errors, dd, ii):
            return jax.lax.scan(body, errors, (dd, ii))
        return scan_fn

    fns = {"dense": make_fn(dense_body), "sparse": make_fn(sparse_body)}
    # interleaved A/B, fastest-of-N per path (see _measure_sparse_ab)
    ts = {"dense": [], "sparse": []}
    for rep in range(reps + 1):
        for key, fn in fns.items():
            errors = jnp.zeros((m, d), jnp.float32)
            t0 = time.perf_counter()
            errors, aggs = fn(errors, deltas, cidx)
            jax.block_until_ready(aggs)
            ts[key].append(time.perf_counter() - t0)
    out = {f"{k}_rounds_per_s": rounds / float(np.min(v[1:]))
           for k, v in ts.items()}
    out["speedup_sparse_vs_dense"] = (out["sparse_rounds_per_s"]
                                      / out["dense_rounds_per_s"])
    return out


def measure_compression_bound(rounds: int, reps: int = 3) -> dict:
    """The sparse-uplink dimension: end-to-end A/B plus the isolated
    uplink+aggregate stage on the compression-bound config (see module
    docstring for the container caveat on the ISSUE's 3x target)."""
    cfg = COMPRESSION
    mc = MLPConfig(**cfg["mlp"])
    d = sum(int(np.prod(s)) for s in
            [(mc.in_dim, mc.hidden), (mc.hidden,),
             (mc.hidden, mc.hidden), (mc.hidden,),
             (mc.hidden, mc.num_classes), (mc.num_classes,)])
    e2e = _measure_sparse_ab(cfg, COMPRESSION_FED_KW, rounds, reps)
    stage = _measure_uplink_stage(d, COMPRESSION_FED_KW["participating"],
                                  COMPRESSION_FED_KW,
                                  max(rounds, 4), reps)
    return {
        "config": dict(COMPRESSION_FED_KW, rounds=rounds, d=d,
                       **{k: v for k, v in cfg.items() if k != "name"}),
        "e2e": e2e,
        "uplink_stage": stage,
        "note": ("sparse = select-once (vals, idx) pipeline, DESIGN.md §3; "
                 "dense = reference encode->decode->dense-mean path. "
                 "See bench_rounds docstring: the ISSUE's >=3x presumes "
                 "accelerator-class HBM-bound aggregation; on this 2-vCPU "
                 "CPU container the dense path's extra passes fuse into "
                 "the round's shared bandwidth-bound streams."),
    }


def measure_server_ingest() -> dict:
    """The one-pass ingest dimension: bytes-moved-per-round for the fused
    ``server_ingest`` jit vs the staged two-pass (aggregate jit + update
    jit) at compression-bound scale, per ``server_state_dtype``. Static
    compile-time measurement (no timing loop): the gate metric is the HLO
    ``bytes`` estimate from launch.hlo_analysis — see module docstring."""
    import dataclasses

    from repro.core.compressors import block_layout
    from repro.core.server_opt import (init_server_state, server_ingest,
                                       server_update)
    from repro.core.stages import server_aggregate_sparse
    from repro.launch.hlo_analysis import analyze

    cfg = COMPRESSION
    mc = MLPConfig(**cfg["mlp"])
    d = sum(int(np.prod(s)) for s in
            [(mc.in_dim, mc.hidden), (mc.hidden,),
             (mc.hidden, mc.hidden), (mc.hidden,),
             (mc.hidden, mc.num_classes), (mc.num_classes,)])
    fed = FedConfig(local_steps=cfg["local_steps"], **COMPRESSION_FED_KW)
    n = fed.participating
    bs, nb = block_layout(d, fed.wire_block)
    k = max(1, int(round(bs * fed.compress_ratio)))

    # gathered (n, nb·k) client selections: one in-block offset per block,
    # global indices — the exact shape the uplink delivers to the server
    rngn = np.random.default_rng(0)
    off = rngn.integers(0, bs, size=(n, nb * k))
    idx = jnp.asarray((np.repeat(np.arange(nb), k)[None, :] * bs
                       + off).astype(np.int32))
    vals = jnp.asarray(rngn.standard_normal((n, nb * k)).astype(np.float32))
    x = jnp.zeros(d, jnp.float32)
    f32_stream = 4.0 * d

    # two-pass: two jits with the dense (d,) mean delta materialized
    # between them (exactly how sim/mesh stage it on the unfused path)
    st = init_server_state(x)
    agg_c = jax.jit(
        lambda v, i: server_aggregate_sparse(v, i, d, n)
    ).lower(vals, idx).compile()
    upd_c = jax.jit(
        lambda s, p, dm: server_update(fed, s, p, dm)
    ).lower(st, x, x).compile()
    agg_hc, upd_hc = analyze(agg_c.as_text()), analyze(upd_c.as_text())
    two_bytes = agg_hc.bytes + upd_hc.bytes
    two = {
        # acc scatter target + dense mean delta + x2/m2/v2/vh2 outputs
        "analytic_streams": 6.0,
        "hlo_bytes": two_bytes,
        "hlo_streams": two_bytes / f32_stream,
        "hlo_rw_bytes": agg_hc.rw_bytes + upd_hc.rw_bytes,
    }

    # fused: one jit per state dtype, dense delta never materialized
    analytic = {
        "float32": 5.0,                      # acc + x2/m2/v2/vh2
        "bfloat16": 4.0,                     # v2/vh2 written at 2 B
        "int8": 3.5 + 2 * 4.0 * nb / f32_stream,   # q codes + block scales
    }
    fused = {}
    for dtype in ("float32", "bfloat16", "int8"):
        fed2 = dataclasses.replace(fed, server_state_dtype=dtype,
                                   fused_ingest="jnp")
        st2 = init_server_state(x, dtype, bs)
        c = jax.jit(
            lambda s, p, v, i, fed2=fed2: server_ingest(
                fed2, s, p, v, i, n, block=bs, impl="jnp")
        ).lower(st2, x, vals, idx).compile()
        hc = analyze(c.as_text())
        fused[dtype] = {
            "analytic_streams": analytic[dtype],
            "hlo_bytes": hc.bytes,
            "hlo_streams": hc.bytes / f32_stream,
            "hlo_rw_bytes": hc.rw_bytes,
            "bytes_reduction_vs_two_pass": two_bytes / hc.bytes,
        }
    return {
        "config": dict(d=d, n=n, block=bs, nb=nb, k=k,
                       algorithm=fed.algorithm, option=fed.option),
        "uplink_bytes": float(vals.nbytes + idx.nbytes),
        "two_pass": two,
        "fused": fused,
        "note": ("gate metric is hlo_bytes (materialized-buffer traffic, "
                 "the repo's §Perf convention): the fused path drops the "
                 "dense (d,) mean-delta stream at every state dtype. "
                 "analytic_streams counts materialized f32-equivalent "
                 "output streams over the d domain (uplink (vals, idx) "
                 "reads, 8·n·nb·k bytes, are identical on both paths and "
                 "excluded). int8 measures above its 3.5-stream analytic "
                 "model because XLA CPU materializes the fp32 v2/vh2 "
                 "intermediates before requantization. hlo_rw_bytes is "
                 "the read+write diagnostic — see module docstring for "
                 "the fp32 CPU recompute caveat."),
    }


def _live_device_bytes() -> int:
    """Total bytes of live jax arrays — the device-residency proxy this
    single-process CPU bench can measure (on CPU the 'device' is host RAM,
    but the accounting is the same buffers an accelerator would hold)."""
    return sum(int(x.nbytes) for x in jax.live_arrays())


def _compiled_mem(sim, st, batch, idx_like):
    """Best-effort XLA memory analysis of the compiled round executable
    (argument/output/temp sizes). None where the backend doesn't report."""
    try:
        core = _CoreState(*st[:5])
        args = (core, batch, jnp.asarray(idx_like), jax.random.PRNGKey(0),
                jnp.int32(0))
        avals = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
            args)
        ma = sim._round_fn.lower(*avals).compile().memory_analysis()
        if ma is None:
            return None
        return {k: int(getattr(ma, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes")
                if hasattr(ma, k)}
    except Exception:
        return None


def _run_scale(m: int, ef_store: bool, rounds: int) -> dict:
    """One scale-out arm: m clients, fixed n-cohort rounds through
    ``FedSim.round`` (per-round loop so live-buffer peaks are observable
    between rounds), prefetch overlapping when the shard store is on."""
    cfg = SCALE
    mc = MLPConfig(**cfg["mlp"])
    n = SCALE_FED_KW["participating"]
    fed = FedConfig(local_steps=cfg["local_steps"], num_clients=m,
                    ef_store=ef_store, **SCALE_FED_KW)
    sim = FedSim(lambda p, b: mlp_loss(p, b, mc), fed)
    data = FederatedClassification(num_clients=m, num_classes=mc.num_classes,
                                   feature_dim=mc.in_dim, seed=0)
    rng = jax.random.PRNGKey(1)
    staged = []
    for r in range(rounds):
        rng, k1, k2 = jax.random.split(rng, 3)
        idx = np.asarray(sample_clients(k1, m, n))
        b = data.round_batches(idx, r, cfg["local_steps"], cfg["batch"])
        staged.append((jax.tree.map(jnp.asarray, b), jnp.asarray(idx), k2))
    baseline = _live_device_bytes()          # staged inputs, no fed state yet
    st = sim.init(pdefs.init_params(mlp_defs(mc), jax.random.PRNGKey(0)))
    # warmup compiles round 0 (and, sharded, materializes warmup shards);
    # the timed run restarts from scratch
    w, _ = sim.round(st, staged[0][0], staged[0][1], staged[0][2])
    jax.block_until_ready(w.params)
    mem = _compiled_mem(sim, w, staged[0][0],
                        np.arange(n, dtype=np.int32) if ef_store
                        else staged[0][1])
    del w
    if ef_store:
        from repro.checkpoint.store import EFStore
        sim._efs = EFStore(m, sim._d)
    if sim.network is not None:
        sim.network = type(sim.network)(sim.network.cfg, m)
        sim.comm_log = type(sim.comm_log)()
    st = sim.init(pdefs.init_params(mlp_defs(mc), jax.random.PRNGKey(0)))
    losses, peak = [], 0
    t0 = time.perf_counter()
    for r in range(rounds):
        b, i, k = staged[r]
        nxt = staged[r + 1][1] if ef_store and r + 1 < rounds else None
        st, met = sim.round(st, b, i, k, prefetch_idx=nxt)
        losses.append(float(met["loss"]))    # per-round sync, loop semantics
        peak = max(peak, _live_device_bytes())
    dt = time.perf_counter() - t0
    return {
        "m": m, "ef_store": ef_store, "d": int(sim._d), "n": n,
        "rounds_per_s": rounds / dt,
        "losses": losses,
        "baseline_live_bytes": int(baseline),
        "peak_live_bytes": int(peak),
        "state_live_bytes": int(peak - baseline),
        "efstore_host_bytes": int(sim._efs.nbytes) if ef_store else 0,
        "compiled_memory": mem,
    }


def measure_scale_out(rounds: int) -> dict:
    """The scale-out dimension: resident-vs-sharded A/B at m=10^4 (must be
    loss-bit-identical), then the sharded m-sweep at fixed cohort size —
    device residency must stay flat while the resident baseline grows with
    m·d. Asserts the sharded peak under 1.25x the analytic device bound."""
    res = _run_scale(10_000, False, rounds)
    shd = _run_scale(10_000, True, rounds)
    assert res["losses"] == shd["losses"], (
        "ef_store must be bit-identical to the resident buffer",
        res["losses"], shd["losses"])
    sweep = {"10000": shd}
    for m in ((100_000,) if QUICK else (100_000, 1_000_000)):
        sweep[str(m)] = _run_scale(m, rounds=rounds, ef_store=True)
    d, n = shd["d"], shd["n"]
    # analytic device bound for the sharded path, independent of m:
    # fed state = params + m/v/v̂ moments + x_client + server_error (6 d
    # fp32 vectors) + the (n, d) cohort EF block; doubled for the
    # donate/update overlap (old + new state both live at the swap), plus
    # the measured pre-state baseline (staged batches, rng keys).
    bound = 2 * (4 * d * (n + 6)) + shd["baseline_live_bytes"]
    for r in sweep.values():
        assert r["peak_live_bytes"] <= 1.25 * bound, (
            "sharded device residency exceeded the analytic bound",
            r["m"], r["peak_live_bytes"], bound)
    return {
        "config": dict(SCALE_FED_KW, rounds=rounds, d=d,
                       **{k: v for k, v in SCALE.items() if k != "name"}),
        "resident_m10k": res,
        "sharded_m10k": shd,
        "loss_bitwise_identical": res["losses"] == shd["losses"],
        "resident_vs_sharded_peak_ratio": (res["peak_live_bytes"]
                                           / shd["peak_live_bytes"]),
        "sweep": sweep,
        "analytic_device_bound_bytes": int(bound),
        "note": ("resident (m, d) EF residency grows with m (231 MB at "
                 "m=10^4, 23 GB at 10^6 — unrunnable); the sharded path "
                 "holds the cohort block only, so peak live bytes stay "
                 "flat across the sweep while the EF state spills to "
                 "lazily materialized host shards (efstore_host_bytes). "
                 "rounds/s on this 1-vCPU CPU container includes host "
                 "gather/scatter + staging; the prefetch overlaps the "
                 "next round's gather with device compute."),
    }


# Seventh dimension (``robustness``, DESIGN.md §robustness): final loss
# and simulated time-to-loss under a (crash_prob × deadline × corruption)
# sweep on a straggler-heavy network (20% stragglers at 8x slowdown —
# wait-for-all pays the straggler max nearly every round, the deadline
# cutoff caps the round at ~2·p50 and drops the stragglers into the EF
# repayment path instead).
ROBUST = dict(name="robustness",
              mlp=dict(in_dim=16, hidden=32, depth=2, num_classes=8),
              local_steps=2, batch=8)
ROBUST_FED_KW = dict(algorithm="fedcams", num_clients=40, participating=16,
                     compressor="blocktopk", compress_ratio=1 / 16,
                     wire_block=256, eta=0.1, eta_l=0.05, wire=True,
                     track_gamma=False)


def _time_to_loss(losses, times, target: float) -> float:
    """Cumulative simulated seconds until the loss first reaches
    ``target`` (inf if it never does)."""
    cum = 0.0
    for l, t in zip(losses, times):
        cum += t
        if l <= target:
            return cum
    return float("inf")


def _stage_robust(rounds: int):
    """Shared staged inputs for every robustness / time-to-loss arm."""
    cfg = ROBUST
    m, n = ROBUST_FED_KW["num_clients"], ROBUST_FED_KW["participating"]
    data = FederatedClassification(num_clients=m,
                                   num_classes=cfg["mlp"]["num_classes"],
                                   feature_dim=cfg["mlp"]["in_dim"], seed=0)
    rng = jax.random.PRNGKey(1)
    idxs, keys, batches = [], [], []
    for r in range(rounds):
        rng, k1, k2 = jax.random.split(rng, 3)
        idx = np.asarray(sample_clients(k1, m, n))
        batches.append(data.round_batches(idx, r, cfg["local_steps"],
                                          cfg["batch"]))
        idxs.append(idx)
        keys.append(k2)
    return (jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *batches),
            jnp.asarray(np.stack(idxs)), jnp.stack(keys))


def _run_robust_arm(staged, fault, deadline_s: float, *,
                    straggler: float = 0.2, async_buffer: int = 0,
                    staleness: str = "inv_sqrt") -> dict:
    """One robustness / time-to-loss arm: scan-driven FedSim run on the
    shared staged inputs and a fresh (deterministic) straggler network.
    With ``async_buffer > 0`` the run goes through the event-driven
    buffered engine, so the per-entry metrics are per FLUSH, not per
    staged cohort. Uplink bytes are reported delivered/attempted
    separately (the CommLog split) — a deadline or async arm's wire bill
    counts only payloads the server saw."""
    from repro.comm import NetworkConfig, SimulatedNetwork
    from repro.comm.faults import FaultConfig  # noqa: F401 (callers build)
    batches, idx, keys = staged
    cfg = ROBUST
    mc = MLPConfig(**cfg["mlp"])
    fed = FedConfig(local_steps=cfg["local_steps"], fault=fault,
                    deadline_s=deadline_s, async_buffer=async_buffer,
                    staleness_weight=staleness, **ROBUST_FED_KW)
    net = SimulatedNetwork(
        NetworkConfig(straggler_prob=straggler, straggler_slowdown=8.0,
                      seed=0),
        ROBUST_FED_KW["num_clients"])
    sim = FedSim(lambda p, b: mlp_loss(p, b, mc), fed, network=net)
    st = sim.init(pdefs.init_params(mlp_defs(mc), jax.random.PRNGKey(0)))
    st, mets = sim.run_rounds(st, batches, idx, keys)
    n = ROBUST_FED_KW["participating"]
    losses = [float(m["loss"]) for m in mets]
    times = [float(m["round_time_s"]) for m in mets]
    return {
        "losses": losses,
        "final_loss": losses[-1],
        "round_times_s": times,
        "sim_time_s": float(np.sum(times)),
        "entries": len(mets),   # cohorts (sync) or flushes (async)
        "uplink_bytes_delivered": int(sim.comm_log.uplink_bytes),
        "uplink_bytes_attempted": int(sim.comm_log.uplink_bytes_attempted),
        "mean_survivors": float(np.mean(
            [float(m.get("survivors", n)) for m in mets])),
        "rejected_total": float(np.sum(
            [float(m.get("rejected", 0.0)) for m in mets])),
        "staleness_max": float(np.max(
            [float(m.get("staleness_max", 0.0)) for m in mets])),
    }


def _probe_dstar(staged, straggler: float) -> float:
    """Deadline for the cutoff arm: ~2·p50 of the round-0 cohort's
    per-client times passes every clean client (jitter included) and
    cuts the 8x stragglers. The probe network is a fresh instance with
    the arm's seed — draws are keyed by (seed, id)/(seed, round, id), so
    the arms see identical links and straggler fates."""
    from repro.comm import NetworkConfig, SimulatedNetwork
    cfg = ROBUST
    net = SimulatedNetwork(
        NetworkConfig(straggler_prob=straggler, straggler_slowdown=8.0,
                      seed=0),
        ROBUST_FED_KW["num_clients"])
    mc = MLPConfig(**cfg["mlp"])
    fed0 = FedConfig(local_steps=cfg["local_steps"], **ROBUST_FED_KW)
    sim0 = FedSim(lambda p, b: mlp_loss(p, b, mc), fed0, network=net)
    sim0.init(pdefs.init_params(mlp_defs(mc), jax.random.PRNGKey(0)))
    timing0 = sim0._round_timing(np.asarray(staged[1][0]), 0)
    return 2.0 * timing0.p50_client_time_s


def measure_robustness(rounds: int) -> dict:
    """The robustness dimension: fault-free baseline, the all-ones-mask
    bitwise-parity arm, the (crash_prob × deadline) grid, and the
    corruption arms. Asserts the acceptance invariants inline — parity is
    bitwise, NaN injection keeps the loss finite and within 2x of
    fault-free, deadline-cutoff time-to-loss beats wait-for-all on the
    straggler-heavy network."""
    from repro.comm.faults import FaultConfig
    cfg = ROBUST
    m, n = ROBUST_FED_KW["num_clients"], ROBUST_FED_KW["participating"]
    staged = _stage_robust(rounds)

    base = _run_robust_arm(staged, None, 0.0)
    parity = _run_robust_arm(staged, FaultConfig(), 0.0)
    assert parity["losses"] == base["losses"], (
        "all-ones fault mask must be bitwise-identical to fault-free",
        base["losses"][:3], parity["losses"][:3])

    dstar = _probe_dstar(staged, 0.2)

    grid = {}
    for crash in (0.0, 0.1, 0.3):
        for dl, dname in ((0.0, "wait_all"), (dstar, "deadline")):
            fault = FaultConfig(crash_prob=crash, seed=1)
            grid[f"crash{crash}_{dname}"] = _run_robust_arm(staged, fault,
                                                            dl)
    corrupt = {}
    for mode in ("nan", "bitflip"):
        corrupt[mode] = _run_robust_arm(
            staged, FaultConfig(corrupt_prob=0.1, corrupt_mode=mode,
                                seed=2), 0.0)
        assert np.isfinite(corrupt[mode]["final_loss"]), mode
        assert corrupt[mode]["rejected_total"] > 0, (
            f"{mode}@0.1 produced no rejections over {rounds} rounds")
    assert corrupt["nan"]["final_loss"] <= 2.0 * base["final_loss"], (
        "NaN injection at 0.1 must stay within 2x of fault-free",
        corrupt["nan"]["final_loss"], base["final_loss"])

    # billing fix regression: a deadline arm bills fewer delivered than
    # attempted uplink bytes (cut stragglers' sends never arrive); the
    # fault-free wait-for-all arm bills everything it attempted
    assert grid["crash0.0_deadline"]["uplink_bytes_delivered"] < \
        grid["crash0.0_deadline"]["uplink_bytes_attempted"]
    assert base["uplink_bytes_delivered"] == base["uplink_bytes_attempted"]

    # acceptance: at straggler_prob >= 0.05 the deadline cutoff reaches
    # the shared loss target in no more simulated time than wait-for-all
    wait, cut = grid["crash0.0_wait_all"], grid["crash0.0_deadline"]
    target = 1.02 * max(wait["final_loss"], cut["final_loss"])
    ttl_wait = _time_to_loss(wait["losses"], wait["round_times_s"], target)
    ttl_cut = _time_to_loss(cut["losses"], cut["round_times_s"], target)
    assert ttl_cut <= ttl_wait, (
        "deadline cutoff must not lose to wait-for-all on the "
        "straggler-heavy network", ttl_cut, ttl_wait)
    return {
        "config": dict(ROBUST_FED_KW, rounds=rounds, deadline_s=dstar,
                       network=dict(straggler_prob=0.2,
                                    straggler_slowdown=8.0),
                       **{k: v for k, v in cfg.items() if k != "name"}),
        "baseline": base,
        "parity_bitwise_identical": parity["losses"] == base["losses"],
        "grid": grid,
        "corruption": corrupt,
        "time_to_loss": {"target": target, "wait_all_s": ttl_wait,
                         "deadline_s": ttl_cut,
                         "speedup": ttl_wait / ttl_cut},
        "note": ("grid arms share staged batches/cohorts and the network "
                 "seed, so loss deltas isolate the fault model; dropped "
                 "clients keep stale EF residuals and repay on rejoin "
                 "(DESIGN.md §robustness)."),
    }


def measure_time_to_loss(rounds: int) -> dict:
    """The time-to-loss dimension (DESIGN.md §11): sync wait-for-all vs
    deadline cutoff vs the event-driven async buffered engine, all on the
    shared staged inputs, swept over straggler probability. The metric is
    cumulative simulated seconds until the loss trajectory first reaches
    a shared target (1.02x the worst arm's final loss, so every arm gets
    there by construction). Asserts the ISSUE acceptance ordering
    async <= deadline <= wait_all at straggler_prob >= 0.2."""
    staged = _stage_robust(rounds)
    buffer = ROBUST_FED_KW["participating"] // 2
    probs = (0.2,) if QUICK else (0.0, 0.2, 0.4)
    sweep = {}
    for sp in probs:
        dstar = _probe_dstar(staged, sp)
        arms = {
            "wait_all": _run_robust_arm(staged, None, 0.0, straggler=sp),
            "deadline": _run_robust_arm(staged, None, dstar, straggler=sp),
            "async": _run_robust_arm(staged, None, 0.0, straggler=sp,
                                     async_buffer=buffer),
        }
        target = 1.02 * max(a["final_loss"] for a in arms.values())
        ttl = {k: _time_to_loss(a["losses"], a["round_times_s"], target)
               for k, a in arms.items()}
        assert all(np.isfinite(t) for t in ttl.values()), (sp, ttl)
        if sp >= 0.2:
            assert ttl["async"] <= ttl["deadline"] <= ttl["wait_all"], (
                "async buffered rounds must reach the target loss no "
                "later than deadline-cutoff and wait-for-all on a "
                "straggler-heavy network", sp, ttl)
        sweep[f"straggler{sp}"] = {
            "deadline_s": dstar,
            "target": target,
            "time_to_loss_s": ttl,
            "speedup_async_vs_wait_all": ttl["wait_all"] / ttl["async"],
            "speedup_async_vs_deadline": ttl["deadline"] / ttl["async"],
            "async_flushes": arms["async"]["entries"],
            "async_staleness_max": arms["async"]["staleness_max"],
            "arms": arms,
        }
    head = sweep["straggler0.2"]
    return {
        "config": dict(ROBUST_FED_KW, rounds=rounds, async_buffer=buffer,
                       staleness_weight="inv_sqrt",
                       straggler_grid=list(probs),
                       network=dict(straggler_slowdown=8.0, seed=0)),
        "sweep": sweep,
        "headline": {
            "straggler_prob": 0.2,
            "speedup_async_vs_wait_all": head["speedup_async_vs_wait_all"],
            "speedup_async_vs_deadline": head["speedup_async_vs_deadline"],
        },
        "note": ("arms share staged batches/cohorts and the network seed "
                 "(draws keyed by (seed, round, client)), so time-to-loss "
                 "deltas isolate the round policy; the async arm's bytes "
                 "bill only delivered payloads (DESIGN.md §11)."),
    }


_MESH_AB_CODE = '''
import json, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import ModelConfig, FedConfig, TrainConfig
from repro.core.mesh import (build_fed_round, build_fed_rounds_scan,
                             fed_batch_defs, fed_state_defs, init_fed_state,
                             scan_batch_specs)
from repro.launch.mesh import make_mesh
from repro.models import params as pdefs
from repro.models.model import Model
from repro.sharding.rules import ParallelContext

ROUNDS, REPS = {rounds}, {reps}
cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
                  dtype="float32")
mesh = make_mesh((8, 1), ("data", "model"))
model = Model(cfg, tp=1)
ctx = ParallelContext(client_axes=("data",), num_clients=8)
K, GB, S = 1, 8, 16
rngn = np.random.default_rng(0)
toks = rngn.integers(0, 64, size=(ROUNDS, K, GB, S)).astype(np.int32)
batch = {{"tokens": jnp.asarray(toks),
          "labels": jnp.asarray(np.roll(toks, -1, -1))}}
seeds = jnp.arange(ROUNDS, dtype=jnp.int32)
steps, out = {{}}, {{}}
for agg in ("dense", "sparse"):
    fed = FedConfig(algorithm="fedcams", num_clients=8, local_steps=K,
                    compressor="blocktopk", compress_ratio=1 / 64,
                    aggregation=agg, client_axes=("data",),
                    eta=0.3, eta_l=0.05, track_gamma=False)
    train = TrainConfig(global_batch=GB, seq_len=S, remat_policy="none")
    rnd = build_fed_round(model, fed, train, ctx)
    sdefs = fed_state_defs(model, fed)
    ssp = jax.tree.map(lambda d: d.spec, sdefs, is_leaf=pdefs.is_def)
    bsp = jax.tree.map(lambda d: d.spec, fed_batch_defs(model, fed, train),
                       is_leaf=pdefs.is_def)
    steps[agg] = (jax.jit(jax.shard_map(
        build_fed_rounds_scan(rnd), mesh=mesh,
        in_specs=(ssp, scan_batch_specs(bsp), P(None)),
        out_specs=(ssp, {{"loss": P(None), "wire_up_bytes": P(None)}})),
        donate_argnums=(0,)), fed)
ts = {{"dense": [], "sparse": []}}
for rep in range(REPS + 1):          # first pair compiles
    for name, (fn, fed) in steps.items():
        state = init_fed_state(model, fed, jax.random.PRNGKey(0))
        t0 = time.perf_counter()
        state, met = fn(state, batch, seeds)
        jax.block_until_ready(state.params)
        ts[name].append(time.perf_counter() - t0)
        out[name + "_final_loss"] = float(np.asarray(met["loss"])[-1])
        out[name + "_wire_up_bytes"] = float(
            np.asarray(met["wire_up_bytes"])[-1])
for name in ts:
    out[name + "_rounds_per_s"] = ROUNDS / float(np.min(ts[name][1:]))
out["speedup_sparse_vs_dense"] = (out["sparse_rounds_per_s"]
                                  / out["dense_rounds_per_s"])
out["wire_reduction"] = (out["dense_wire_up_bytes"]
                         / out["sparse_wire_up_bytes"])
print(json.dumps(out))
'''


def measure_mesh_sparse_ab(rounds: int, reps: int = 3) -> dict:
    """Mesh-backend sparse-vs-dense aggregation A/B: the scan-driven mesh
    round on a forced-8-device subprocess (the bench process itself must
    keep seeing one device, like the tests — tests/conftest.py note), tiny
    transformer, blocktopk 1/64, compacted-Selection gather vs dense psum.
    On this CPU host ``mesh_sparse_impl`` auto-resolves to the jnp
    ``Compressor.select`` provider (interpret-mode Pallas would measure
    the interpreter, not the kernel); both providers emit the
    bit-identical Selection (tests/test_mesh_parity.py), so the payload
    numbers are provider-independent. CPU-mesh collectives are
    shared-memory copies, so the rounds/s ratio is NOT the accelerator
    story — the load-bearing numbers are ``wire_reduction`` (the
    collective payload ratio, measured by ``mesh_wire_bytes`` == the
    traced gather operands) and the loss parity between the paths."""
    import json as _json
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"   # a host mesh: the chip stays ours
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    code = _MESH_AB_CODE.format(rounds=rounds, reps=reps)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1800)
    assert r.returncode == 0, r.stderr[-4000:]
    out = _json.loads(r.stdout.strip().splitlines()[-1])
    out["config"] = dict(compressor="blocktopk", ratio=1 / 64, clients=8,
                         rounds=rounds, reps=reps, scan=True)
    return out


def measure_local_rules(rounds: int) -> dict:
    """The local-rule dimension (core/local.py): scan-driver throughput per
    rule on the overhead-bound config. sgd is the pre-split round — its
    number is the stage-split regression gate; sgdm/prox show what the
    extra local state/ops cost inside the same scanned pipeline."""
    cfg = OVERHEAD
    data = FederatedClassification(num_clients=FED_KW["num_clients"],
                                   num_classes=cfg["mlp"]["num_classes"],
                                   feature_dim=cfg["mlp"]["in_dim"], seed=0)
    batches, idx, keys, _ = _stage(data, cfg, rounds)
    out = {}
    for rule_kw in ({"local_opt": "sgd"},
                    {"local_opt": "sgdm"},
                    {"local_opt": "prox"},
                    {"local_opt": "sgd", "eta_l_decay": 0.99},
                    {"local_opt": "sgd", "local_steps_min": 1}):
        name = rule_kw["local_opt"]
        if "eta_l_decay" in rule_kw:
            name = "sgd+decay"
        elif "local_steps_min" in rule_kw:
            name = "sgd+heteroK"
        mc = MLPConfig(**cfg["mlp"])
        kw = dict(FED_KW, **{k: cfg[k] for k in ("eta", "eta_l") if k in cfg})
        kw.update(rule_kw)
        fed = FedConfig(local_steps=cfg["local_steps"], **kw)
        sim = FedSim(lambda p, b, mc=mc: mlp_loss(p, b, mc), fed)
        st = sim.init(pdefs.init_params(mlp_defs(mc), jax.random.PRNGKey(0)))
        _run_scan(sim, st, batches, idx, keys)  # warmup
        st = _fresh_state(sim, cfg)
        t0 = time.perf_counter()
        st, met = _run_scan(sim, st, batches, idx, keys)
        jax.block_until_ready(st.params)
        out[name] = {"scan_rounds_per_s": rounds / (time.perf_counter() - t0),
                     "final_loss": met["loss"]}
    return out


def main():
    rounds = 30 if QUICK else 120
    payload = {
        "suite": "fedsim_rounds",
        # The ISSUE's >=5x target presumes driver-overhead-dominated
        # rounds. On this container the bench_wire_e2e round body is
        # compute-bound (per-client matmuls at ~20 GFLOP/s on 2 vCPUs), so
        # the measured e2e ratio is bounded near 2x; overhead_bound shows
        # the driver itself clears 5x once round math stops dominating.
        "note": ("speedups are vs the reconstructed pre-PR per-round "
                 "driver ('legacy'); see module docstring for the "
                 "compute-bound vs overhead-bound regimes"),
    }
    rows = []
    for cfg in (E2E, OVERHEAD):
        p = measure(cfg, rounds)
        payload[cfg["name"]] = p
        rows.append(csv_row(
            f"rounds_{cfg['name']}_legacy", 1e6 * (1 / p["legacy_rounds_per_s"]),
            f"rounds_per_s={p['legacy_rounds_per_s']:.1f}"))
        rows.append(csv_row(
            f"rounds_{cfg['name']}_scan", 1e6 * (1 / p["scan_rounds_per_s"]),
            f"rounds_per_s={p['scan_rounds_per_s']:.1f};"
            f"speedup_vs_legacy={p['speedup_scan_vs_legacy']:.1f}x;"
            f"speedup_vs_loop={p['speedup_scan_vs_loop']:.1f}x;"
            f"wire_MBps={p['scan_wire_bytes_per_s']/1e6:.1f}"))
    lr = measure_local_rules(rounds)
    payload["local_rules"] = lr
    for name, p in lr.items():
        rows.append(csv_row(
            f"rounds_local_{name}", 1e6 * (1 / p["scan_rounds_per_s"]),
            f"rounds_per_s={p['scan_rounds_per_s']:.1f}"))
    cb = measure_compression_bound(4 if QUICK else 8, reps=3 if QUICK else 5)
    payload["compression_bound"] = cb
    rows.append(csv_row(
        "rounds_compression_bound_sparse",
        1e6 * (1 / cb["e2e"]["sparse_rounds_per_s"]),
        f"rounds_per_s={cb['e2e']['sparse_rounds_per_s']:.2f};"
        f"e2e_speedup_vs_dense={cb['e2e']['speedup_sparse_vs_dense']:.2f}x;"
        f"uplink_stage_speedup="
        f"{cb['uplink_stage']['speedup_sparse_vs_dense']:.2f}x"))
    si = measure_server_ingest()
    payload["server_ingest"] = si
    rows.append(csv_row(
        "rounds_server_ingest_fused_f32",
        float("nan"),   # a byte count, not a time: no time is measured here
        f"hlo_bytes={si['fused']['float32']['hlo_bytes']:.0f};"
        f"hlo_streams={si['fused']['float32']['hlo_streams']:.2f};"
        f"two_pass_streams={si['two_pass']['hlo_streams']:.2f};"
        "bytes_reduction="
        f"{si['fused']['float32']['bytes_reduction_vs_two_pass']:.2f}x;"
        "bf16_reduction="
        f"{si['fused']['bfloat16']['bytes_reduction_vs_two_pass']:.2f}x;"
        "int8_reduction="
        f"{si['fused']['int8']['bytes_reduction_vs_two_pass']:.2f}x"))
    ab = measure_mesh_sparse_ab(8 if QUICK else 24, reps=2 if QUICK else 4)
    payload["mesh_sparse_ab"] = ab
    rows.append(csv_row(
        "rounds_mesh_sparse_ab",
        1e6 * (1 / ab["sparse_rounds_per_s"]),
        f"rounds_per_s={ab['sparse_rounds_per_s']:.1f};"
        f"speedup_vs_dense={ab['speedup_sparse_vs_dense']:.2f}x;"
        f"wire_reduction={ab['wire_reduction']:.1f}x"))
    rb = measure_robustness(20 if QUICK else 60)
    payload["robustness"] = rb
    rows.append(csv_row(
        "rounds_robustness_deadline",
        1e6 * rb["time_to_loss"]["deadline_s"],
        f"ttl_speedup_vs_wait_all={rb['time_to_loss']['speedup']:.2f}x;"
        f"parity_bitwise={rb['parity_bitwise_identical']};"
        f"nan_final_loss={rb['corruption']['nan']['final_loss']:.3f};"
        f"base_final_loss={rb['baseline']['final_loss']:.3f};"
        f"crash0.3_deadline_loss="
        f"{rb['grid']['crash0.3_deadline']['final_loss']:.3f}"))
    ttl = measure_time_to_loss(20 if QUICK else 60)
    payload["time_to_loss"] = ttl
    hd = ttl["sweep"]["straggler0.2"]
    rows.append(csv_row(
        "rounds_async_time_to_loss",
        1e6 * hd["time_to_loss_s"]["async"],
        f"speedup_vs_wait_all={hd['speedup_async_vs_wait_all']:.2f}x;"
        f"speedup_vs_deadline={hd['speedup_async_vs_deadline']:.2f}x;"
        f"async_flushes={hd['async_flushes']};"
        f"staleness_max={hd['async_staleness_max']:.0f}"))
    so = measure_scale_out(4 if QUICK else 6)
    payload["scale_out"] = so
    for m, r in so["sweep"].items():
        rows.append(csv_row(
            f"rounds_scale_out_m{m}", 1e6 * (1 / r["rounds_per_s"]),
            f"rounds_per_s={r['rounds_per_s']:.2f};"
            f"peak_live_MB={r['peak_live_bytes']/1e6:.1f};"
            f"efstore_host_MB={r['efstore_host_bytes']/1e6:.1f}"))
    rows.append(csv_row(
        "rounds_scale_out_resident_m10000",
        1e6 * (1 / so["resident_m10k"]["rounds_per_s"]),
        f"rounds_per_s={so['resident_m10k']['rounds_per_s']:.2f};"
        f"peak_live_MB={so['resident_m10k']['peak_live_bytes']/1e6:.1f};"
        f"peak_ratio_vs_sharded="
        f"{so['resident_vs_sharded_peak_ratio']:.1f}x;"
        f"loss_bitwise={so['loss_bitwise_identical']}"))
    update_bench_json(payload)
    return rows


if __name__ == "__main__":
    for row in main():
        print(row)
