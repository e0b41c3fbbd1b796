"""What the plain references share: parameter leaves, the precision every
matrix product runs in, norms, the layer-stack plan and the loss.

A reference is straightforward ``jax.numpy`` in float32. Every matrix
product goes through a :class:`Dot`: ``Dot()`` is the reference itself
(float32 operands, ``Precision.HIGHEST``); ``Dot(fp8=True)`` is the
control, the same reference computing in float8 where the program
computes in bfloat16: every product's operands and result, and the
residual stream between layers, rounded to the e4m3 grid under
per-tensor scales (norms, softmax and the loss stay float32, as the
program keeps them). It is the step below the bfloat16 compute that the
configurations state.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
#: Largest finite e4m3 magnitude; smallest e4m3 normal exponent.
E4M3_MAX, E4M3_EMIN = 448.0, -6


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One parameter: shape, how it is drawn, and its scale."""
    shape: Tuple[int, ...]
    init: str = "normal"     # normal | zeros | ones
    scale: float = 1.0


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def linear(n_in: int, *out) -> Leaf:
    return Leaf((n_in,) + tuple(out), scale=n_in ** -0.5)


def stacked(tree, n: int):
    return jax.tree.map(lambda l: dataclasses.replace(l, shape=(n,) + l.shape),
                        tree, is_leaf=is_leaf)


def e4m3(x):
    """``x`` rounded to the e4m3 grid after scaling its largest magnitude
    to 448 (round half to even, subnormals below 2**-6, no overflow)."""
    amax = lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    y = x / s
    _, e = jnp.frexp(y)                       # |y| = m * 2**e, m in [0.5, 1)
    step = jnp.exp2(jnp.maximum(e - 4, E4M3_EMIN - 3).astype(jnp.float32))
    q = jnp.clip(jnp.round(y / step) * step, -E4M3_MAX, E4M3_MAX)
    return x + lax.stop_gradient(q * s - x)


@dataclasses.dataclass(frozen=True)
class Dot:
    """Matrix products and stored activations at the reference's precision
    (``fp8=False``) or the control's (``fp8=True``: on the e4m3 grid in the
    forward pass, gradients passed straight through in float32)."""
    fp8: bool = False

    def __call__(self, eqn, a, b):
        out = jnp.einsum(eqn, self.act(a), self.act(b), precision=HIGHEST,
                         preferred_element_type=jnp.float32)
        return self.act(out)

    def act(self, x):
        """An activation as the compute precision stores it."""
        return e4m3(x) if self.fp8 else x


def rms_norm(scale, x, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def plan(kinds):
    """(period, repeats, tail) of a per-layer kind list: the shortest
    period that repeats, scanned as stacked groups, and leftover layers."""
    n = len(kinds)
    for p in range(1, n + 1):
        reps = n // p
        if all(kinds[i] == kinds[i % p] for i in range(reps * p)):
            return tuple(kinds[:p]), reps, tuple(kinds[reps * p:])
    raise AssertionError("unreachable")


def layer_kinds(cfg) -> list:
    pat = cfg.get("block_pattern", ["attn"])
    return [pat[i % len(pat)] for i in range(cfg["num_layers"])]


def stack_defs(cfg, layer_defs):
    group, reps, tail = plan(layer_kinds(cfg))
    out = {"groups": stacked({f"l{j}": layer_defs(cfg, k)
                              for j, k in enumerate(group)}, reps)}
    if tail:
        out["tail"] = {f"t{j}": layer_defs(cfg, k) for j, k in enumerate(tail)}
    return out


def run_stack(p, x, cfg, layer, dot):
    """All layers over a full sequence; each period recomputed in the
    backward pass (the numbers are the same, the memory one period's)."""
    group, _, tail = plan(layer_kinds(cfg))

    @jax.checkpoint
    def period(x, gp):
        for j, kind in enumerate(group):
            x = dot.act(layer(gp[f"l{j}"], x, cfg, kind, dot))
        return x

    x, _ = lax.scan(lambda x, gp: (period(x, gp), None), dot.act(x),
                    p["groups"])
    for j, kind in enumerate(tail):
        x = dot.act(layer(p["tail"][f"t{j}"], x, cfg, kind, dot))
    return x


def xent(logits, labels):
    """Mean cross entropy over every position."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - lab)


def head(params, h, labels, cfg, dot):
    h = rms_norm(params["final_norm"], h, cfg["norm_eps"])
    logits = dot("bsd,vd->bsv", h, params["unembed"]["table"])
    return xent(logits, labels)


def embed_and_head_defs(cfg):
    d, v = cfg["d_model"], cfg["vocab_size"]
    return {"embed": {"table": Leaf((v, d))},
            "final_norm": Leaf((d,), "ones"),
            "unembed": {"table": Leaf((v, d), scale=d ** -0.5)}}
