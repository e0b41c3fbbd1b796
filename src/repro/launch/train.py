"""Production training driver: federated LM training on a jax mesh.

On real TPU hardware this drives the full production mesh; in this
container it runs the same code path on small host meshes (the smoke
configs train end-to-end on CPU). Examples:

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --smoke \
        --rounds 20 --algorithm fedcams --compressor topk
    PYTHONPATH=src python -m repro.launch.train --arch xlstm-350m --smoke \
        --dp 4 --tp 2 --devices 8 --rounds 10

Each round (or scanned chunk of rounds) is a profiler step ``round``
holding the host spans ``stage`` (its batch made and put on the device),
``dispatch`` (the jitted call) and ``sync`` (its loss read back); the
round's device operations carry the layer scopes of
``repro.core.stages.LAYER_SCOPES``. ``--trace-dir DIR`` writes the
profiler's trace of steps 1..``--trace-rounds`` (after compilation) into
``DIR``.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time


def main(argv=None) -> dict:
    """Parse ``argv``, train, and return ``{"history", "compile_s",
    "state", "kernels"}``: per-round losses and seconds, the round step's
    compile time (None on the scan path), the final federated state, and
    how the kernels ran (compiled / interpret / off)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-trainable)")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights and the synthetic client data")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--devices", type=int, default=0,
                    help="force host device count (set before jax import)")
    ap.add_argument("--algorithm", default="fedcams")
    ap.add_argument("--compressor", default="topk")
    ap.add_argument("--ratio", type=float, default=1.0 / 64.0)
    ap.add_argument("--aggregation", default="dense")
    ap.add_argument("--agg-groups", type=int, default=1,
                    help="two-level hierarchical sparse aggregation "
                         "(DESIGN.md §scale-out): split the --dp clients "
                         "into this many edge groups; each group merges its "
                         "members' (vals, idx) selections into one dense "
                         "partial and only the g partials reach the root. "
                         "Requires a sparse --compressor and dp %% groups "
                         "== 0; 1 = flat single-level aggregation")
    ap.add_argument("--mesh-sparse-impl", default="auto",
                    choices=("auto", "kernel", "jnp"),
                    help="sparse-aggregation selection provider (DESIGN.md "
                         "§3): the fused Pallas topk_ef_sparse kernel vs "
                         "the jnp Compressor.select path; auto = kernel "
                         "where it compiles (TPU), jnp elsewhere. NB "
                         "forcing 'kernel' implies --use-kernels (the "
                         "whole KernelImpl: fused server update + EF too)")
    ap.add_argument("--fused-ingest", default="auto",
                    choices=("auto", "kernel", "jnp", "off"),
                    help="one-pass fused server ingest (DESIGN.md §3): "
                         "scatter-mean + FedAMS update in a single "
                         "read-modify-write over optimizer state, no "
                         "dense mean delta. auto = fuse where the round "
                         "is eligible (sparse blocktopk aggregation), "
                         "kernel Pallas where it compiles (TPU). NB "
                         "forcing 'kernel' implies --use-kernels")
    ap.add_argument("--server-state-dtype", default="float32",
                    choices=("float32", "bfloat16", "int8"),
                    help="server second-moment (v, v̂) storage dtype; "
                         "bf16 halves optimizer-state HBM residency "
                         "(int8-blockscale is FedSim-only)")
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--local-opt", default="sgd",
                    choices=("sgd", "sgdm", "prox"),
                    help="local-update rule (core/local.py, DESIGN.md §8)")
    ap.add_argument("--local-momentum", type=float, default=0.9,
                    help="heavy-ball beta for --local-opt sgdm")
    ap.add_argument("--prox-mu", type=float, default=0.01,
                    help="proximal strength for --local-opt prox")
    ap.add_argument("--eta-l-decay", type=float, default=1.0,
                    help="per-round local LR decay (round t trains at "
                         "eta_l * decay^t; 1.0 = constant)")
    ap.add_argument("--local-steps-min", type=int, default=0,
                    help="heterogeneous per-client local work: client i "
                         "runs K_i ~ U{min..K} steps (0 = homogeneous)")
    ap.add_argument("--participating", type=int, default=0)
    ap.add_argument("--crash-prob", type=float, default=0.0,
                    help="fault injection (DESIGN.md §robustness): P(a "
                         "client crashes per round); crashed clients drop "
                         "out of the masked survivor aggregate and keep "
                         "stale EF residuals")
    ap.add_argument("--corrupt-prob", type=float, default=0.0,
                    help="P(a delivered payload was damaged in transit); "
                         "the server validates before ingest and rejects "
                         "offenders (needs --aggregation sparse with a "
                         "topk-family --compressor)")
    ap.add_argument("--corrupt-mode", default="nan",
                    choices=("nan", "inf", "bitflip", "truncate"))
    ap.add_argument("--max-update-norm", type=float, default=0.0,
                    help="per-client L2 clip applied to validated payloads "
                         "before ingest (0 = off)")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="FedSim wire-mode only; the mesh driver rejects "
                         "it (no transport clock) — model stragglers as "
                         "crashes here")
    ap.add_argument("--async-buffer", type=int, default=0,
                    help="FedSim wire-mode only (DESIGN.md §11): fire a "
                         "buffered async aggregation every this-many "
                         "deliveries instead of waiting for the cohort "
                         "(0 = synchronous); the mesh driver rejects it")
    ap.add_argument("--staleness-weight", default="inv_sqrt",
                    choices=("inv_sqrt", "uniform", "inv_linear", "exp"),
                    help="async flush weight w(τ) per buffered entry, τ = "
                         "server versions since its dispatch")
    ap.add_argument("--eta", type=float, default=0.5)
    ap.add_argument("--eta-l", type=float, default=0.05)
    ap.add_argument("--use-kernels", action="store_true")
    ap.add_argument("--scan-rounds", type=int, default=0,
                    help="scan this many rounds per device dispatch "
                         "(0/1 = one jitted call per round)")
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--trace-dir", default="",
                    help="write a profiler trace (jax.profiler, host spans "
                         "and device operations) of steps 1..--trace-rounds "
                         "into this directory")
    ap.add_argument("--trace-rounds", type=int, default=3,
                    help="how many steps --trace-dir traces")
    args = ap.parse_args(argv)
    if args.trace_rounds < 1:
        ap.error("--trace-rounds must be at least 1")

    if args.devices:
        # a host mesh of forced CPU devices: never the chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import FedConfig, TrainConfig
    from repro.configs.registry import get_arch
    from repro.core.mesh import init_fed_state, jit_fed_round
    from repro.data.synthetic import FederatedLMData
    from repro.kernels.ops import default_kernel_impl
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.models.model import Model

    enable_compile_cache()

    spec = get_arch(args.arch)
    cfg = spec.smoke if args.smoke else spec.model
    num_clients = args.dp
    if args.agg_groups > 1:
        # two-level aggregation: the client axis splits into (group, member)
        # so tier 1 gathers run over "data" and tier 2 over "cgroup"
        if args.dp % args.agg_groups:
            ap.error(f"--dp {args.dp} not divisible by "
                     f"--agg-groups {args.agg_groups}")
        mesh = make_mesh((args.agg_groups, args.dp // args.agg_groups,
                          args.tp), ("cgroup", "data", "model"))
        client_axes = ("cgroup", "data")
    else:
        mesh = make_mesh((args.dp, args.tp), ("data", "model"))
        client_axes = ("data",) if args.dp > 1 else ()
    if args.deadline_s > 0:
        ap.error("--deadline-s is FedSim wire-mode only — the mesh driver "
                 "has no transport clock to cut against; use --crash-prob "
                 "to model dropouts here")
    if args.async_buffer > 0:
        ap.error("--async-buffer is FedSim wire-mode only — the event-"
                 "driven buffered engine needs the simulated transport "
                 "clock's per-client delivery times, which the mesh "
                 "driver does not model")
    fault = None
    if args.crash_prob > 0 or args.corrupt_prob > 0 \
            or args.max_update_norm > 0:
        from repro.comm.faults import FaultConfig
        fault = FaultConfig(crash_prob=args.crash_prob,
                            corrupt_prob=args.corrupt_prob,
                            corrupt_mode=args.corrupt_mode,
                            max_update_norm=args.max_update_norm,
                            seed=args.fault_seed)
    fed = FedConfig(algorithm=args.algorithm, compressor=args.compressor,
                    compress_ratio=args.ratio, aggregation=args.aggregation,
                    agg_groups=args.agg_groups,
                    mesh_sparse_impl=args.mesh_sparse_impl,
                    fused_ingest=args.fused_ingest,
                    server_state_dtype=args.server_state_dtype,
                    local_steps=args.local_steps, num_clients=num_clients,
                    local_opt=args.local_opt,
                    local_momentum=args.local_momentum,
                    prox_mu=args.prox_mu, eta_l_decay=args.eta_l_decay,
                    local_steps_min=args.local_steps_min,
                    participating=args.participating, eta=args.eta,
                    eta_l=args.eta_l,
                    client_axes=client_axes,
                    # the γ diagnostic consumes the full-cohort dense mean,
                    # which a partial (fault-tolerant) round never computes
                    track_gamma=fault is None,
                    fault=fault)
    train = TrainConfig(global_batch=args.global_batch, seq_len=args.seq_len,
                        rounds=args.rounds, remat_policy="none",
                        seed=args.seed)
    model = Model(cfg, tp=args.tp)

    # the compiled kernels on TPU (so `auto` resolves to them there); off
    # TPU only when a flag forces them — forcing the selection provider or
    # the ingest implies the whole KernelImpl (fused server update and
    # dense-path EF too), exactly as --use-kernels
    kernel_impl = default_kernel_impl(
        forced=args.use_kernels or args.mesh_sparse_impl == "kernel"
        or args.fused_ingest == "kernel")
    # the federated state is donated: params/opt-moments/EF errors update
    # in place instead of being copied every round
    step = jit_fed_round(model, fed, train, mesh, kernel_impl=kernel_impl)
    scan_step = None
    if args.scan_rounds and args.scan_rounds > 1:
        scan_step = jit_fed_round(model, fed, train, mesh,
                                  kernel_impl=kernel_impl, scan=True)
    state = init_fed_state(model, fed, jax.random.PRNGKey(train.seed),
                           mesh=mesh)
    nparams = sum(int(np.prod(l.shape))
                  for l in jax.tree.leaves(state.params))
    kernels = ("off" if kernel_impl is None else
               "compiled" if kernel_impl.compiled else "interpret")
    print(f"arch={cfg.name} params={nparams/1e6:.1f}M clients={num_clients} "
          f"algo={fed.algorithm}/{fed.compressor} mesh={args.dp}x{args.tp} "
          f"kernels={kernels}")

    data = FederatedLMData(num_clients=max(num_clients, 1),
                           vocab_size=cfg.vocab_size, seed=train.seed)
    history = []
    span = jax.profiler.TraceAnnotation
    window = contextlib.ExitStack()

    @contextlib.contextmanager
    def round_step(i, r):
        """Loop step ``i`` as the profiler step ``round`` numbered ``r``.
        The profiler starts before step 1 and stops after step
        ``--trace-rounds``."""
        if args.trace_dir and i == 1:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans need no Python trace
            window.enter_context(jax.profiler.trace(args.trace_dir,
                                                    profiler_options=opts))
        elif i == args.trace_rounds + 1:
            window.close()
        with jax.profiler.StepTraceAnnotation("round", step_num=r):
            yield

    def log(r, loss, seconds, extra=""):
        history.append({"round": r, "loss": loss, "seconds": seconds})
        if r % args.log_every == 0 or r == train.rounds - 1:
            print(f"round {r:4d}  loss {loss:8.4f}  {extra}({seconds:.3f}s)",
                  flush=True)

    with window:                  # a trace that outlives the rounds stops
        if scan_step is not None:
            from repro.core.mesh import stage_mesh_rounds
            r = i = 0
            while r < train.rounds:
                chunk = min(args.scan_rounds, train.rounds - r)
                with round_step(i, r):
                    t0 = time.perf_counter()
                    with span("stage"):
                        batch, seeds = stage_mesh_rounds(
                            data, r, chunk, fed.local_steps,
                            train.global_batch, train.seq_len)
                    with span("dispatch"):
                        state, met = scan_step(state, batch, seeds)
                    with span("sync"):
                        losses = np.asarray(met["loss"])  # one sync a chunk
                per = (time.perf_counter() - t0) / chunk
                for j in range(chunk):
                    log(r + j, float(losses[j]), per)
                r, i = r + chunk, i + 1
            compile_s = None
        else:
            def batch_of(r):
                raw = data.mesh_batch(r, fed.local_steps, train.global_batch,
                                      train.seq_len)
                return {k: jnp.asarray(v) for k, v in raw.items()}

            t0 = time.perf_counter()
            step = step.lower(state, batch_of(0), jnp.int32(0)).compile()
            compile_s = time.perf_counter() - t0
            print(f"compiled round step in {compile_s:.1f}s", flush=True)
            for r in range(train.rounds):
                with round_step(r, r):
                    t0 = time.perf_counter()
                    with span("stage"):
                        batch = batch_of(r)
                    with span("dispatch"):
                        state, met = step(state, batch, jnp.int32(r))
                    with span("sync"):
                        loss = float(met["loss"])  # waits for the round
                extra = ""
                if "survivors" in met:
                    extra = (f"surv {float(met['survivors']):3.0f}  "
                             f"rej {float(met['rejected']):3.0f}  ")
                log(r, loss, time.perf_counter() - t0, extra)
    if args.checkpoint:
        from repro.checkpoint import save_pytree
        save_pytree(args.checkpoint, jax.device_get(state._asdict()),
                    {"arch": cfg.name, "rounds": train.rounds})
        print(f"checkpoint -> {args.checkpoint}")
    return {"history": history, "compile_s": compile_s, "state": state,
            "kernels": kernels}


if __name__ == "__main__":
    main()
