"""Scaled-sign + fused error feedback as a Pallas TPU kernel.

Two-pass structure (the scale  ‖x+e‖₁/d  is a global reduction):
  pass 1: blockwise |·| partial sums (kernel below, accumulated in fp32);
  pass 2: elementwise  hat = scale·sign(x+e),  err = (x+e) − hat,
          with the scalar scale broadcast to every tile.

On TPU the sign bits would additionally be packed 8→1 into int8 lanes for
the wire (see core.stages.packed_sign_leaf for the collective side); the
kernel emits the dense hat used by the local error-feedback update.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import interpret_arg, out_struct, row_grid

DEFAULT_BLOCK = 2048


def _l1_partial_kernel(x_ref, e_ref, out_ref):
    out_ref[...] = jnp.sum(jnp.abs(x_ref[...] + e_ref[...]), axis=1,
                           keepdims=True)


def _sign_ef_kernel(scale_ref, x_ref, e_ref, hat_ref, err_ref):
    tot = x_ref[...] + e_ref[...]
    # sign(0) := +1, matching make_sign and the 1-bit wire format (a 1-bit
    # lane cannot carry a third "zero" state)
    hat = scale_ref[...] * jnp.where(tot >= 0, 1.0, -1.0)
    hat_ref[...] = hat
    err_ref[...] = tot - hat


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sign_ef(x, err, *, block: int = DEFAULT_BLOCK, interpret=None):
    """x, err: (N,) fp32 with N % block == 0 (block % 128 == 0 to
    compile for TPU).
    Returns (hat, new_err)."""
    assert x.ndim == 1 and x.shape == err.shape
    n = x.shape[0]
    assert n % block == 0, (n, block)
    nb = n // block
    grid, r = row_grid(nb)
    mat = (nb, block)
    spec = pl.BlockSpec((r, block), lambda i: (i, 0))
    interpret = interpret_arg(interpret)
    xm, em = x.reshape(mat), err.reshape(mat)

    partials = pl.pallas_call(
        _l1_partial_kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((r, 1), lambda i: (i, 0)),
        out_shape=out_struct((nb, 1), x.dtype, x),
        interpret=interpret,
        name="sign_ef_l1",
    )(xm, em)
    scale = (jnp.sum(partials) / n).reshape(1, 1)

    hat, ne = pl.pallas_call(
        _sign_ef_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), spec, spec],
        out_specs=[spec, spec],
        out_shape=(out_struct(mat, x.dtype, x), out_struct(mat, x.dtype, x)),
        interpret=interpret,
        name="sign_ef",
    )(scale, xm, em)
    return hat.reshape(-1), ne.reshape(-1)
