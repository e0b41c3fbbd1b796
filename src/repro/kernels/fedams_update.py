"""Fused FedAMS server update as a Pallas TPU kernel.

One HBM pass over five operand streams (x, m, v, v̂, Δ̂) producing four
outputs — the unfused jnp version reads/writes each array separately (9+
passes). The update is purely elementwise so it tiles trivially: 1-D blocks
sized to keep 9 fp32 streams resident in VMEM.

Implements both paper options with ``server_update``'s op sequence
(:func:`repro.core.server_opt.fedams_step`), so x/m/v/v̂ agree with it bit
for bit:
  option 1:  v̂ = max(v̂, v, ε);  x += η·m·rsqrt(v̂)
  option 2:  v̂ = max(v̂, v);     x += η·m/(√v̂+ε)

Ragged sizes are handled by zero-padding the operands to a block multiple
and slicing the outputs back: pad lanes carry d=0 so every output pad lane
is a constant that the slice discards.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.server_opt import fedams_step
from repro.kernels.common import interpret_arg, out_struct

DEFAULT_BLOCK = 4096


def _fedams_kernel(x_ref, m_ref, v_ref, vh_ref, d_ref,
                   x_out, m_out, v_out, vh_out, *,
                   eta: float, beta1: float, beta2: float, eps: float,
                   option: int):
    inc, m2, v2, vh2 = fedams_step(m_ref[...], v_ref[...], vh_ref[...],
                                   d_ref[...], eta=eta, beta1=beta1,
                                   beta2=beta2, eps=eps, option=option)
    x_out[...] = x_ref[...] + inc
    m_out[...] = m2
    v_out[...] = v2
    vh_out[...] = vh2


@functools.partial(jax.jit, static_argnames=("eta", "beta1", "beta2", "eps",
                                             "option", "block", "interpret"))
def fedams_update(x, m, v, vhat, delta, *, eta: float, beta1: float,
                  beta2: float, eps: float, option: int = 1,
                  block: int = DEFAULT_BLOCK, interpret=None):
    """All inputs (N,) fp32, any N. Returns (x, m, v, vhat)."""
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x, m, v, vhat, delta = (jnp.pad(a, (0, pad))
                                for a in (x, m, v, vhat, delta))
    np_ = n + pad
    grid = (np_ // block,)
    spec = pl.BlockSpec((block,), lambda i: (i,))
    out_shape = tuple(out_struct((np_,), jnp.float32, x) for _ in range(4))
    outs = pl.pallas_call(
        functools.partial(_fedams_kernel, eta=eta, beta1=beta1, beta2=beta2,
                          eps=eps, option=option),
        grid=grid,
        in_specs=[spec] * 5,
        out_specs=[spec] * 4,
        out_shape=out_shape,
        interpret=interpret_arg(interpret),
        name="fedams_update",
    )(x, m, v, vhat, delta)
    if pad:
        outs = tuple(o[:n] for o in outs)
    return outs
