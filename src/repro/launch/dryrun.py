import os
# the dry-run compiles on 512 host CPU devices, never on an attached chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and extract roofline terms.

This is the proof (without hardware) that the distribution config is
coherent: a sharding mismatch, an unsupported collective or a spec error
fails the compile. Results stream into a JSON file so long sweeps are
resumable.

Usage:
    python -m repro.launch.dryrun --arch all --shape all --mesh single \
        --out results/dryrun.json
    python -m repro.launch.dryrun --arch deepseek-v3-671b --shape train_4k \
        --mesh multi
"""

import argparse
import json
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--algorithm", default="fedcams")
    ap.add_argument("--compressor", default="topk")
    ap.add_argument("--aggregation", default="dense")
    ap.add_argument("--ratio", type=float, default=1.0 / 64.0)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--delta-dtype", default="float32",
                    help="wire dtype for the dense client collective")
    ap.add_argument("--xlstm-chunkwise", type=int, default=0,
                    help="chunk size for chunkwise-recurrent mLSTM (0=off)")
    ap.add_argument("--moe-cf", type=float, default=0.0,
                    help="override MoE capacity factor (0=config default)")
    ap.add_argument("--tp-collective", default="psum",
                    choices=["psum", "rs_ag"])
    ap.add_argument("--shard-server-state", action="store_true")
    ap.add_argument("--overwrite", action="store_true",
                    help="recompute cases already present in --out")
    args = ap.parse_args()

    # imports AFTER the XLA flag is set
    import jax  # noqa: E402
    from repro.configs import ARCH_IDS, INPUT_SHAPES, FedConfig, TrainConfig
    from repro.configs.registry import get_arch
    from repro.launch.hlo_analysis import analyze
    from repro.launch.mesh import backend_spec, make_production_mesh
    from repro.launch.roofline import model_flops_for, roofline_from_hlo
    from repro.launch.steps import build_step, shape_allowed

    # the production meshes are v5e pods: their roofline terms are a
    # model against that chip's published peaks, not a measurement
    target = backend_spec("TPU v5 lite")
    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    import dataclasses

    fed = FedConfig(algorithm=args.algorithm, compressor=args.compressor,
                    compress_ratio=args.ratio, aggregation=args.aggregation,
                    local_steps=args.local_steps, delta_dtype=args.delta_dtype,
                    shard_server_state=args.shard_server_state)
    train = TrainConfig(remat_policy=args.remat,
                        tp_collective=args.tp_collective)

    def apply_variants(spec):
        cfg = spec.model
        if args.xlstm_chunkwise and cfg.xlstm is not None:
            cfg = dataclasses.replace(
                cfg, xlstm=dataclasses.replace(
                    cfg.xlstm, chunkwise=True,
                    chunk_size=args.xlstm_chunkwise))
        if args.moe_cf and cfg.moe is not None:
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe,
                                             capacity_factor=args.moe_cf))
        if cfg is not spec.model:
            spec = dataclasses.replace(spec, model=cfg)
        return spec

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    mesh_cache = {}
    for multi in meshes:
        mesh_name = "pod2x16x16" if multi else "pod16x16"
        if multi not in mesh_cache:
            mesh_cache[multi] = make_production_mesh(multi_pod=multi)
        mesh = mesh_cache[multi]
        chips = mesh.devices.size
        for arch in archs:
            spec = apply_variants(get_arch(arch))
            for shape_name in shapes:
                shape = INPUT_SHAPES[shape_name]
                key = f"{args.tag}/{mesh_name}/{arch}/{shape_name}"
                cached = results.get(key, {})
                if cached.get("status") in ("ok", "skipped") and not args.overwrite:
                    print(f"[skip-cached] {key}")
                    continue
                ok, why = shape_allowed(spec, shape)
                if not ok:
                    results[key] = {"status": "skipped", "reason": why}
                    print(f"[skip] {key}: {why}")
                    _flush(args.out, results)
                    continue
                t0 = time.time()
                try:
                    bundle = build_step(spec, shape, mesh, fed, train,
                                        chunk=args.chunk)
                    lowered = bundle.lower()
                    t_lower = time.time() - t0
                    compiled = lowered.compile()
                    t_compile = time.time() - t0 - t_lower
                    try:
                        mem = compiled.memory_analysis()
                        mem_d = {
                            "argument_size": getattr(mem, "argument_size_in_bytes", None),
                            "output_size": getattr(mem, "output_size_in_bytes", None),
                            "temp_size": getattr(mem, "temp_size_in_bytes", None),
                            "generated_code_size": getattr(mem, "generated_code_size_in_bytes", None),
                        }
                    except Exception as e:  # pragma: no cover
                        mem_d = {"error": str(e)}
                    cost = compiled.cost_analysis() or {}
                    cost = {k: float(v) for k, v in cost.items()
                            if isinstance(v, (int, float)) and k in
                            ("flops", "bytes accessed", "transcendentals")}
                    hc = analyze(compiled.as_text())
                    if shape.kind == "train":
                        tokens = shape.global_batch * shape.seq_len
                        mf = model_flops_for(bundle.model.cfg, "train", tokens,
                                             fed.local_steps)
                    elif shape.kind == "prefill":
                        mf = model_flops_for(bundle.model.cfg, "prefill",
                                             shape.global_batch * shape.seq_len)
                    else:
                        mf = model_flops_for(bundle.model.cfg, "decode",
                                             shape.global_batch)
                    rl = roofline_from_hlo(hc, chips=chips, model_flops=mf,
                                           spec=target)
                    results[key] = {
                        "status": "ok",
                        "description": bundle.description,
                        "lower_s": round(t_lower, 1),
                        "compile_s": round(t_compile, 1),
                        "memory": mem_d,
                        "xla_cost_analysis_raw": cost,
                        "collectives": {
                            "bytes_by_kind": hc.coll_bytes,
                            "count_by_kind": hc.coll_count,
                        },
                        "roofline": rl.to_dict(),
                    }
                    print(f"[ok] {key}: compute={rl.compute_s:.3e}s "
                          f"memory={rl.memory_s:.3e}s "
                          f"collective={rl.collective_s:.3e}s "
                          f"dominant={rl.dominant} useful={rl.useful_ratio:.2f} "
                          f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
                except Exception as e:
                    results[key] = {"status": "error", "error": str(e)[-2000:],
                                    "traceback": traceback.format_exc()[-4000:]}
                    print(f"[ERROR] {key}: {e}")
                _flush(args.out, results)


def _flush(path, results):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1)
    os.replace(tmp, path)


if __name__ == "__main__":
    main()
