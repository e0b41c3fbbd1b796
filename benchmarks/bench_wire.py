"""Wire-format benchmarks: encode/decode throughput per codec and measured
wire bytes vs the paper's analytic bit counts (Table 1 made concrete), plus
the end-to-end simulated round time for a FedSim wire-mode run."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import QUICK, csv_row

from repro.comm import make_wire_codec, measured_vs_analytic


def _time(fn, *args, reps: int = 20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6, out


def bench_codec(name: str, d: int, ratio: float = 1 / 64):
    codec = make_wire_codec(name, ratio)
    x = jnp.asarray(np.random.default_rng(0).normal(size=d), jnp.float32)
    enc = jax.jit(codec.encode)
    dec = jax.jit(lambda b: codec.decode(b, d))
    us_enc, buf = _time(enc, x)
    us_dec, out = _time(dec, buf)
    r = measured_vs_analytic(codec, d)
    exact = bool(jnp.all(out == codec.compressor.compress(x).reshape(-1)))
    mbps = d * 4 / (us_enc / 1e6) / 1e6
    return csv_row(
        f"wire_{name}_d{d}", us_enc + us_dec,
        f"encode_us={us_enc:.0f};decode_us={us_dec:.0f};"
        f"encode_MBps={mbps:.0f};wire_bytes={r['measured_bytes']};"
        f"analytic_bits={r['analytic_bits']};"
        f"overhead_bits={r['overhead_bits']};exact={exact}")


def main():
    d = 100_000 if QUICK else 11_200_000
    rows = [bench_codec(name, d)
            for name in ("dense32", "topk", "blocktopk", "sign")]

    # end-to-end: a small FedCAMS run with wire=True through the simulated
    # network — cumulative measured bytes and simulated seconds per codec
    from repro.configs.base import FedConfig, TrainConfig
    from repro.core.api import FederatedTrainer
    from repro.data.synthetic import FederatedClassification
    from repro.models import params as pdefs
    from repro.models.convmixer import MLPConfig, mlp_defs, mlp_loss
    mc = MLPConfig(in_dim=32, hidden=64, depth=2, num_classes=10)
    rounds = 10 if QUICK else 60
    for comp in ("topk", "sign"):
        tr = FederatedTrainer(
            fed=FedConfig(algorithm="fedcams", num_clients=50,
                          participating=10, local_steps=3, compressor=comp,
                          compress_ratio=1 / 64, eta=0.1, eta_l=0.05,
                          wire=True),
            train=TrainConfig(rounds=rounds, log_every=10**6),
            loss_fn=lambda p, b: mlp_loss(p, b, mc),
            init_params=pdefs.init_params(mlp_defs(mc), jax.random.PRNGKey(0)))
        tr.data = FederatedClassification(num_clients=50, feature_dim=32,
                                          seed=0)
        t0 = time.time()
        hist = tr.run(log=None)
        rows.append(csv_row(
            f"wire_e2e_{comp}", (time.time() - t0) / rounds * 1e6,
            f"rounds={rounds};wire_MB={hist[-1]['wire_bytes']/1e6:.2f};"
            f"sim_time_s={hist[-1]['sim_time_s']:.2f};"
            f"loss={hist[-1]['loss']:.3f}"))
    return rows


if __name__ == "__main__":
    for row in main():
        print(row)
