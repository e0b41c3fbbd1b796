"""jit'd pytree wrappers around the Pallas kernels.

``KernelImpl`` plugs into ``core.mesh.build_fed_round(kernel_impl=...)``:
it provides the same (hat, new_err) / server-update contracts as the jnp
path but runs the compress + update math through the fused kernels. Leaves
are flattened and zero-padded to a block multiple (zero padding is exact for
both compressors: pad elements produce hat=0 / carry err=0; the l1 scale
uses the true element count).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig
from repro.core.compressors import Compressor, Selection
from repro.core.server_opt import ServerState
from repro.kernels.common import resolve_interpret
from repro.kernels.fedams_update import fedams_update as _fedams_update
from repro.kernels.sign_ef import sign_ef as _sign_ef
from repro.kernels.topk_ef import topk_ef as _topk_ef
from repro.kernels.topk_ef import topk_ef_sparse as _topk_ef_sparse


def _pad_flat(x, block):
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.size
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, n


@dataclass(frozen=True)
class KernelImpl:
    """``interpret=None`` (the default) lets the platform decide
    (:func:`~repro.kernels.common.resolve_interpret`): the interpreter on
    the CPU platform only, compiled Pallas everywhere else — so a
    ``KernelImpl`` on TPU runs the real kernels or fails to compile."""

    block: int = 2048
    interpret: Optional[bool] = None

    @property
    def _interp(self) -> bool:
        return resolve_interpret(self.interpret)

    @property
    def compiled(self) -> bool:
        """True when the kernels run as compiled Pallas rather than the
        interpreter — what ``mesh_sparse_impl='auto'`` keys off."""
        return not self._interp

    # -- error-feedback compression ------------------------------------
    def ef_compress_leaf(self, comp_name: str, ratio: float, x, err):
        from repro.core.compressors import block_layout
        if comp_name in ("topk", "blocktopk"):
            bs, _ = block_layout(x.size, self.block)
            flat, n = _pad_flat(x, bs)
            eflat, _ = _pad_flat(err, bs)
            k = max(1, int(round(ratio * bs)))
            hat, ne = _topk_ef(flat, eflat, k=k, block=bs,
                                 interpret=self._interp)
        elif comp_name in ("sign", "packedsign"):
            flat, n = _pad_flat(x, self.block)
            eflat, _ = _pad_flat(err, self.block)
            # scale over the padded vector differs from mean over n; rescale
            hat, ne = _sign_ef(flat, eflat, block=self.block,
                                 interpret=self._interp)
            if flat.size != n:
                hat = hat * (flat.size / n)
                ne = (flat + eflat) - hat
        else:
            raise ValueError(f"no kernel for compressor {comp_name!r}")
        hat = hat[:n].reshape(x.shape)
        ne = ne[:n].reshape(err.shape)
        return hat, ne

    def topk_select_leaf(self, ratio: float, x, err):
        """Fused EF + compacted selection for one leaf (the sparse-uplink
        kernel form): returns ``(Selection, new_err)`` where the Selection's
        ``idx`` are flat positions in the zero-padded domain (entries past
        ``x.size`` carry 0.0, matching
        :meth:`repro.core.compressors.Compressor.select`'s padded-block
        convention) and ``new_err`` has ``x``'s shape.

        This is the TPU entry point for the select-once pipeline
        (DESIGN.md §3): one HBM pass per tile emits the compacted block.
        ``mesh_uplink``'s sparse aggregation routes through it (via
        :meth:`topk_select_tree`) when ``fed.mesh_sparse_impl`` resolves
        to the kernel; the sim backend and the CPU-platform mesh use
        the jnp ``Compressor.select`` (compiled XLA beats interpret-mode
        Pallas on the CPU)."""
        from repro.core.compressors import block_layout
        bs, _ = block_layout(x.size, self.block)
        flat, n = _pad_flat(x, bs)
        eflat, _ = _pad_flat(err, bs)
        k = max(1, int(round(ratio * bs)))
        vals, idx, ne = _topk_ef_sparse(flat, eflat, k=k, block=bs,
                                        interpret=self._interp)
        sel = Selection(vals=vals.reshape(-1), idx=idx.reshape(-1))
        return sel, ne[:n].reshape(err.shape)

    def topk_select_tree(self, ratio: float, delta, err, mask):
        """Fused select-once uplink for every leaf of this device's shard
        tree — the kernel sibling of
        :func:`repro.core.stages.topk_select_tree` (identical contract):
        per leaf one ``topk_ef_sparse`` HBM pass emits the compacted
        ``(vals, idx)`` Selection AND the EF residual; no dense hat is
        materialized anywhere. Non-participating clients (``mask == 0``)
        contribute zero values and keep their error unchanged.

        Returns ``(sel_tree, err_tree)`` with
        :class:`~repro.core.compressors.Selection` leaves whose ``idx``
        are flat positions in each leaf's zero-padded block domain —
        bit-identical to ``Compressor.select`` on ``delta + err``
        (tests/test_kernels.py)."""
        from repro.core.stages import select_tree
        return select_tree(
            lambda d, e: self.topk_select_leaf(ratio, d, e),
            delta, err, mask)

    def ef_compress_tree(self, comp: Compressor, delta, err, mask):
        name = comp.name.split("_")[0]
        ratio = comp.ratio

        def leaf(d, e):
            return self.ef_compress_leaf(name, ratio, d, e)

        flat_d, tdef = jax.tree_util.tree_flatten(delta)
        flat_e = jax.tree_util.tree_leaves(err)
        hats, errs = [], []
        for d, e in zip(flat_d, flat_e):
            h, ne = leaf(d, e)
            hats.append(jnp.where(mask > 0, h, jnp.zeros_like(h)))
            errs.append(jnp.where(mask > 0, ne, e))
        return (jax.tree_util.tree_unflatten(tdef, hats),
                jax.tree_util.tree_unflatten(tdef, errs))

    # -- fused server update ---------------------------------------------
    def fedams_update_tree(self, fed: FedConfig, st: ServerState, params, agg):
        """Same update math as the jnp ``server_update`` for the whole
        {fedams, fedcams, fedamsgrad} × {option 1, 2} grid (fedamsgrad IS
        Option 2, same mapping as the jnp branches): m/v/v̂ bit-identical;
        x within a few ulp across the two differently-shaped programs
        (XLA may contract the x division into an FMA/rsqrt form —
        regression-tested in tests/test_server_opt.py, which also owns
        the same-shape bitwise gate). bf16 v/v̂ storage is dequantized by
        the fp32 pad and requantized by the output cast — the same
        round-trip the quantized ``server_update`` wrapper runs."""
        option = 2 if fed.algorithm == "fedamsgrad" else fed.option
        flat_p, tdef = jax.tree_util.tree_flatten(params)
        flat_m = jax.tree_util.tree_leaves(st.m)
        flat_v = jax.tree_util.tree_leaves(st.v)
        flat_vh = jax.tree_util.tree_leaves(st.vhat)
        flat_d = jax.tree_util.tree_leaves(agg)
        xs, ms, vs, vhs = [], [], [], []
        for x, m, v, vh, d in zip(flat_p, flat_m, flat_v, flat_vh, flat_d):
            xf, n = _pad_flat(x, self.block)
            mf, _ = _pad_flat(m, self.block)
            vf, _ = _pad_flat(v, self.block)
            vhf, _ = _pad_flat(vh, self.block)
            df, _ = _pad_flat(d, self.block)
            x2, m2, v2, vh2 = _fedams_update(
                xf, mf, vf, vhf, df, eta=fed.eta, beta1=fed.beta1,
                beta2=fed.beta2, eps=fed.eps, option=option,
                block=self.block, interpret=self._interp)
            xs.append(x2[:n].reshape(x.shape).astype(x.dtype))
            ms.append(m2[:n].reshape(x.shape))
            vs.append(v2[:n].reshape(x.shape).astype(v.dtype))
            vhs.append(vh2[:n].reshape(x.shape).astype(vh.dtype))
        unf = lambda ls: jax.tree_util.tree_unflatten(tdef, ls)
        return unf(xs), ServerState(m=unf(ms), v=unf(vs), vhat=unf(vhs),
                                    t=st.t + 1)

    # -- one-pass fused ingest (DESIGN.md §3) ------------------------------
    def fedams_ingest_tree(self, fed: FedConfig, st: ServerState, params,
                           sels, n_div, gather):
        """Kernel-routed one-pass server ingest: per leaf, gather the
        compacted client Selections and run ``kernels.fedams_ingest``
        (scatter-mean + FedAMS step + state dequant/requant in one pass —
        no dense mean delta). Same contract as
        :func:`repro.core.server_opt.server_ingest_tree` with
        ``impl='kernel'`` and this impl's block/interpret."""
        from repro.core.server_opt import server_ingest_tree
        return server_ingest_tree(fed, st, params, sels, n_div, gather,
                                  block=self.block, impl="kernel",
                                  interpret=self.interpret)


def default_kernel_impl(forced: bool = False) -> Optional[KernelImpl]:
    """The kernels an entry point runs with no flag set: a
    :class:`KernelImpl` on TPU, where they compile, so ``auto`` resolves
    to them; ``None`` on the CPU platform, where the interpreter would
    lose to compiled XLA — unless ``forced``."""
    if forced or jax.default_backend() == "tpu":
        return KernelImpl()
    return None
