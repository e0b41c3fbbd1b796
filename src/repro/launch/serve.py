"""Serving driver: prefill a batch of prompts, then greedy-decode.

Runs the same prefill/decode step functions the dry-run lowers for the
production mesh, on a small host mesh (smoke configs on CPU):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
        --batch 4 --prompt-len 16 --gen 16
"""
from __future__ import annotations

import argparse
import os
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args(argv)

    if args.devices:
        # a host mesh of forced CPU devices: never the chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.configs.registry import get_arch
    from repro.launch.mesh import make_mesh
    from repro.models import params as pdefs
    from repro.models.model import Model, greedy_sample
    from repro.sharding.rules import ParallelContext

    spec = get_arch(args.arch)
    cfg = spec.smoke if args.smoke else spec.model
    if cfg.is_encoder:
        raise SystemExit(f"{cfg.name} is encoder-only; no decode serving")
    max_len = args.max_len or (args.prompt_len + args.gen)
    model = Model(cfg, tp=args.tp)
    ctx = ParallelContext(model_axis="model" if args.tp > 1 else None,
                          tp=args.tp)

    params = model.init(jax.random.PRNGKey(0))
    if args.checkpoint:
        from repro.checkpoint import load_pytree
        params, meta = load_pytree(args.checkpoint, params)
        print(f"restored {meta}")

    if args.tp > 1:
        mesh = make_mesh((args.tp,), ("model",))
        pspecs = jax.tree.map(lambda d: d.spec, model.defs(),
                              is_leaf=pdefs.is_def)
        cdefs = model.cache_defs(args.batch, max_len, seq_sharded=False)
        cspecs = jax.tree.map(lambda d: d.spec, cdefs, is_leaf=pdefs.is_def)

        prefill = jax.jit(jax.shard_map(
            lambda p, t: model.prefill(p, t, ctx, max_len=max_len),
            mesh=mesh, in_specs=(pspecs, P()),
            out_specs=(P("model"), cspecs)))

        def dstep(p, t, c, pos):
            lg, c2 = model.decode_step(p, t, c, pos, ctx, max_len=max_len)
            return greedy_sample(lg, ctx), c2

        decode = jax.jit(jax.shard_map(
            dstep, mesh=mesh, in_specs=(pspecs, P(), cspecs, P()),
            out_specs=(P(), cspecs)))
    else:
        prefill = jax.jit(lambda p, t: model.prefill(p, t, ctx, max_len=max_len))

        def dstep(p, t, c, pos):
            lg, c2 = model.decode_step(p, t, c, pos, ctx, max_len=max_len)
            return greedy_sample(lg, ctx), c2

        decode = jax.jit(dstep)

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           size=(args.batch, args.prompt_len)).astype(np.int32)
    t0 = time.time()
    logits, caches = prefill(params, jnp.asarray(prompts))
    tok = greedy_sample(logits, ctx)[:, None].astype(jnp.int32)
    t_prefill = time.time() - t0

    out = [np.asarray(tok[:, 0])]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.int32(args.prompt_len + i)
        tok, caches = decode(params, tok, caches, pos)
        tok = tok[:, None].astype(jnp.int32)
        out.append(np.asarray(tok[:, 0]))
    t_dec = time.time() - t0
    gen = np.stack(out, 1)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"prefill: {t_prefill*1e3:.1f} ms   decode: "
          f"{t_dec/max(args.gen-1,1)*1e3:.2f} ms/token  "
          f"({args.batch*(args.gen-1)/max(t_dec,1e-9):.1f} tok/s)")
    for b in range(min(args.batch, 4)):
        print(f"  seq[{b}]: {prompts[b, -4:].tolist()} -> {gen[b].tolist()}")


if __name__ == "__main__":
    main()
