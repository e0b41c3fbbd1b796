"""The comparison that decides ``correct``: the program's first federated
rounds against a plain FedCAMS reference from the same seeded weights and
the same batches.

The reference round, in float32 (or the float8 control, see
:class:`reference.common.Dot`):

1. each client runs K steps of local SGD on its own rows from the round's
   global weights: ``x <- x - eta_l * grad``, its loss the mean of the K;
2. its delta plus its error-feedback residual is cut into blocks of the
   leaf's block layout, and the k largest magnitudes of each block are
   kept (k = round(ratio * block)); the residual keeps the rest;
3. the server averages the kept values over the clients and takes the
   FedAMS step of the paper (Algorithm 2, option 1):
   ``m = b1 m + (1 - b1) d``, ``v = b2 v + (1 - b2) d^2``,
   ``vh = max(vh, v, eps)``, ``x = x + eta m / sqrt(vh)``.

Three numbers are compared, each against a limit of its own
(``limits`` in the cell's workload file):

* ``loss_gap`` — the largest relative gap of a round's loss;
* ``grad_gap`` — the first gradient as the server's optimizer gets it
  (the round-0 aggregate, read from its momentum ``m = (1 - b1) d``): the
  largest gap between the program's and the reference's norm of a leaf,
  over the reference's norm of that leaf or of the median leaf, whichever
  is larger;
* ``change_gap`` — the same of the norm of each leaf's change over the
  checked rounds, over the leaves whose reference first gradient is at
  least a thousandth of the median leaf's (a leaf with a gradient of
  nought, such as a token table an encoder never reads, moves by nothing
  in either).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import weights
from reference.common import Dot

ELIGIBLE = 1e-3


def block_layout(n: int, block: int = 2048):
    """Block size (a multiple of 128, at most ``block``) and block count
    of a leaf of ``n`` elements, zero-padded to whole blocks."""
    bs = min(block, -(-n // 128) * 128)
    return bs, -(-n // bs)


def keep_per_block(bs: int, ratio: float) -> int:
    return max(1, int(round(ratio * bs)))


def select(tot, ratio: float, block: int = 2048):
    """Blockwise top-k of a flat vector: ``(values, padded indices,
    residual)``; ties go to the lower index."""
    n = tot.size
    bs, nb = block_layout(n, block)
    k = keep_per_block(bs, ratio)
    tb = jnp.pad(tot, (0, nb * bs - n)).reshape(nb, bs)
    _, idx = jax.lax.top_k(jnp.abs(tb), k)
    vals = jnp.take_along_axis(tb, idx, axis=1)
    rows = jnp.arange(nb)[:, None]
    resid = tb.at[rows, idx].set(0.0).reshape(-1)[:n]
    return vals.reshape(-1), (idx + rows * bs).reshape(-1), resid


class Reference:
    """The reference FedCAMS round of one cell, at ``dot``'s precision."""

    def __init__(self, cell, ref_module, defs, dot: Dot, devices):
        self.cell, self.defs, self.devices = cell, defs, devices
        fed, mix = cell.workload["fed"], cell.traffic
        self.fed, self.mix = fed, mix
        cfg = cell.config
        loss = functools.partial(ref_module.loss, cfg=cfg, dot=dot)

        def client(x, e, batches):
            def step(p, b):
                l, g = jax.value_and_grad(lambda q: loss(q, b))(p)
                return jax.tree.map(lambda a, gg: a - fed["eta_l"] * gg,
                                    p, g), l

            with jax.default_matmul_precision("highest"):
                local, losses = jax.lax.scan(step, x, batches)
            out = jax.tree.map(
                lambda a, b_, ee: select((a - b_ + ee).reshape(-1),
                                         fed["compress_ratio"]),
                local, x, e)
            is_t = lambda t: isinstance(t, tuple)
            part = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=is_t)
            new_e = jax.tree.map(lambda r, ee: r.reshape(ee.shape),
                                 part(2), e)
            return jnp.mean(losses), part(0), part(1), new_e

        def add(agg, vals, idx):
            return jax.tree.map(
                lambda a, v, i: a.at[i].add(v, mode="drop"), agg, vals, idx)

        def server(x, m, v, vh, agg):
            n = mix["clients"]
            b1, b2 = fed["beta1"], fed["beta2"]

            def leaf(x, m, v, vh, a):
                d = a[:x.size].reshape(x.shape) / n
                m = b1 * m + (1 - b1) * d
                v = b2 * v + (1 - b2) * d * d
                vh = jnp.maximum(jnp.maximum(vh, v), fed["eps"])
                return x + fed["eta"] * m / jnp.sqrt(vh), m, v, vh

            out = jax.tree.map(leaf, x, m, v, vh, agg)
            is_t = lambda t: isinstance(t, tuple)
            return tuple(jax.tree.map(lambda t: t[i], out, is_leaf=is_t)
                         for i in range(4))

        self.client = jax.jit(client, donate_argnums=(1,))
        self.add = jax.jit(add, donate_argnums=(0,))
        self.server = jax.jit(server, donate_argnums=(0, 1, 2, 3))
        self.norms = jax.jit(weights.leaf_norms)
        self.change = jax.jit(functools.partial(weights.change_norms,
                                                defs=defs))
        self.init = jax.jit(functools.partial(weights.generate, defs))
        self.zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        self.blocks = jax.jit(lambda t: jax.tree.map(
            lambda a: jnp.zeros(math.prod(block_layout(a.size)),
                                jnp.float32), t))

    def run(self, key, batches, traffic) -> dict:
        """The readings of the checked rounds: ``losses``, round-0 momentum
        norms ``m1`` and ``change`` norms after the last round."""
        dev0 = self.devices[0]
        n = self.mix["clients"]
        with jax.default_device(dev0):
            x = self.init(key)
            zeros = self.zeros
            m, v, vh = zeros(x), zeros(x), zeros(x)
            errs = [None] * n
            out = {"losses": []}
            for r, batch in enumerate(batches):
                agg = self.blocks(x)
                loss = 0.0
                for c in range(n):
                    e = (zeros(x) if errs[c] is None
                         else jax.device_put(errs[c], dev0))
                    errs[c] = None
                    rows = jax.device_put(traffic.client_batch(batch, c), dev0)
                    l, vals, idx, e = self.client(x, e, rows)
                    agg = self.add(agg, vals, idx)
                    loss += float(l)
                    errs[c] = jax.device_put(e, self.devices[c % len(
                        self.devices)])
                    del e
                out["losses"].append(loss / n)
                x, m, v, vh = self.server(x, m, v, vh, agg)
                if r == 0:
                    out["m1"] = to_host(self.norms(m))
            out["change"] = to_host(self.change(x, key=key))
        return out


def to_host(tree: dict) -> dict:
    return {k: float(v) for k, v in tree.items()}


def leaf_gaps(prog: dict, ref: dict, key: str, leaves) -> dict:
    """Per leaf: the gap of the program's norm from the reference's, over
    the reference's norm of that leaf or of the median leaf, the larger."""
    r = {k: ref[key][k] for k in leaves}
    med = float(np.median(list(r.values())))
    return {k: abs(prog[key][k] - v) / max(v, med) for k, v in r.items()}


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers of the module docstring, from the program's and
    the reference's readings."""
    lp, lr = np.array(prog["losses"]), np.array(ref["losses"])
    med = float(np.median(list(ref["m1"].values())))
    keep = [k for k, g in ref["m1"].items() if g >= ELIGIBLE * med]
    grad = leaf_gaps(prog, ref, "m1", list(ref["m1"]))
    change = leaf_gaps(prog, ref, "change", keep)
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_gap": max(grad.values()),
            "change_gap": max(change.values())}


def verdict(numbers: dict, limits: dict) -> bool:
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)
