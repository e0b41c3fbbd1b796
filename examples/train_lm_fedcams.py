"""End-to-end driver: federated training of a transformer LM with FedCAMS.

Runs the full production code path (Model substrate + mesh fed_round via
shard_map) on host devices. The default preset is a ~10M-param gemma-2-style
model federated over 4 clients with 2-way tensor parallelism — a few
hundred rounds are CPU-feasible; --preset 100m scales the same config up.

    PYTHONPATH=src python examples/train_lm_fedcams.py --rounds 200
    PYTHONPATH=src python examples/train_lm_fedcams.py --preset 100m \
        --rounds 300   # the assignment's ~100M-model target
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ap = argparse.ArgumentParser()
ap.add_argument("--preset", default="10m", choices=["2m", "10m", "100m"])
ap.add_argument("--rounds", type=int, default=100)
ap.add_argument("--clients", type=int, default=4)
ap.add_argument("--tp", type=int, default=2)
ap.add_argument("--seq-len", type=int, default=128)
ap.add_argument("--global-batch", type=int, default=8)
ap.add_argument("--compressor", default="topk")
ap.add_argument("--ratio", type=float, default=1 / 64)
ap.add_argument("--checkpoint", default="")
args = ap.parse_args()

# a host mesh of forced CPU devices: never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault(
    "XLA_FLAGS",
    f"--xla_force_host_platform_device_count={args.clients * args.tp}")

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import FedConfig, ModelConfig, TrainConfig
from repro.core import (build_fed_round, fed_batch_defs, fed_state_defs,
                        init_fed_state)
from repro.data import FederatedLMData
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.models import params as pdefs
from repro.sharding.rules import ParallelContext

SIZES = {  # layers, d_model, heads, kv, d_ff, vocab
    "2m": (2, 128, 4, 2, 384, 512),
    "10m": (4, 256, 8, 4, 768, 2048),
    "100m": (12, 768, 12, 4, 2048, 8192),
}
L, D, H, KV, FF, V = SIZES[args.preset]
cfg = ModelConfig(name=f"lm-{args.preset}", family="dense", num_layers=L,
                  d_model=D, num_heads=H, num_kv_heads=KV, d_ff=FF,
                  vocab_size=V, attn_pattern=(64, 0), logit_softcap=30.0,
                  dtype="float32")
fed = FedConfig(algorithm="fedcams", compressor=args.compressor,
                compress_ratio=args.ratio, num_clients=args.clients,
                local_steps=2, eta=0.3, eta_l=0.05, client_axes=("data",))
train = TrainConfig(global_batch=args.global_batch, seq_len=args.seq_len,
                    remat_policy="none")

mesh = make_mesh((args.clients, args.tp), ("data", "model"))
model = Model(cfg, tp=args.tp)
ctx = ParallelContext(model_axis="model" if args.tp > 1 else None,
                      tp=args.tp, client_axes=("data",),
                      num_clients=args.clients)
sdefs = fed_state_defs(model, fed)
ssp = jax.tree.map(lambda d: d.spec, sdefs, is_leaf=pdefs.is_def)
bsp = jax.tree.map(lambda d: d.spec, fed_batch_defs(model, fed, train),
                   is_leaf=pdefs.is_def)
step = jax.jit(jax.shard_map(build_fed_round(model, fed, train, ctx),
                                mesh=mesh, in_specs=(ssp, bsp, P()),
                                out_specs=(ssp, {"loss": P(),
                                                 "wire_up_bytes": P()})))
state = init_fed_state(model, fed, jax.random.PRNGKey(0))
nparams = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state.params))
print(f"model={cfg.name} params={nparams/1e6:.1f}M clients={args.clients} "
      f"tp={args.tp} compressor={fed.compressor} r={fed.compress_ratio:g}")

data = FederatedLMData(num_clients=args.clients, vocab_size=V)
t0 = time.time()
for r in range(args.rounds):
    raw = data.mesh_batch(r, fed.local_steps, args.global_batch, args.seq_len)
    state, met = step(state, {k: jnp.asarray(v) for k, v in raw.items()},
                      jnp.int32(r))
    if r % 10 == 0 or r == args.rounds - 1:
        print(f"round {r:4d}  loss {float(met['loss']):7.4f}  "
              f"wire {float(met['wire_up_bytes'])/1e6:6.2f} MB/round  "
              f"({time.time()-t0:6.1f}s)")
if args.checkpoint:
    from repro.checkpoint import save_pytree
    save_pytree(args.checkpoint, jax.device_get(state.params),
                {"preset": args.preset, "rounds": args.rounds})
    print("saved", args.checkpoint)
