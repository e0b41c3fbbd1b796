"""What every Pallas kernel of this package shares: the interpret-mode
decision, output structs typed for ``shard_map``, and the block-row tiling.

Tiling: a flat vector of ``nb`` selection blocks is viewed as the
``(nb, block)`` matrix (a free reshape) and a grid step takes
:func:`row_grid` whole rows, so each block is one sublane row of the
tile and per-block reductions run along the lanes. ``block`` is a multiple
of 128; a tile whose rows are not a multiple of 8 only occurs when it
spans the whole matrix, which keeps every block shape aligned to the TPU's
(8, 128) tiling. Most kernels take :data:`ROWS` rows a step; the top-k
kernels choose their own rows from the block size
(:func:`repro.kernels.topk_ef.tile_rows`): their per-block selection is a
serial chain of cross-lane reductions, and only a taller tile gives it
independent sublane groups to overlap.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl

#: Selection blocks per grid step (one per sublane of an f32 vreg).
ROWS = 8


def resolve_interpret(interpret=None) -> bool:
    """Whether a Pallas kernel runs in interpret mode: ``None`` means the
    platform decides — the interpreter on the CPU platform only. Anywhere
    else the kernel compiles, or the run fails; nothing falls back."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def interpret_arg(interpret=None):
    """``pallas_call``'s ``interpret`` for :func:`resolve_interpret`'s
    decision: the TPU interpreter (which types ``shard_map``'s varying mesh
    axes through the kernel body) or ``False`` to compile."""
    if resolve_interpret(interpret):
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.InterpretParams()
    return False


def out_struct(shape, dtype, like) -> jax.ShapeDtypeStruct:
    """An output of ``shape``/``dtype`` that varies over the same manual
    mesh axes as ``like`` — ``pallas_call`` under ``shard_map`` needs the
    ``vma`` of every output stated."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def row_grid(nb: int, rows: int = ROWS):
    """``(grid, rows_per_step)`` for a kernel over an ``(nb, ·)`` matrix:
    ``rows`` per step, or all ``nb`` when there are fewer (a block equal
    to the full dim is always aligned). The last step may be partial; its
    out-of-range rows are computed on padding and never written back."""
    r = rows if nb >= rows else nb
    return (pl.cdiv(nb, r),), r
