"""Server-side optimizers: the paper's FedAMS family plus all baselines.

The server treats the aggregated client delta Δ̂_t as a pseudo-gradient
(paper eq. 3.2-3.4) and performs one adaptive step. Note the *sign*: the
global update is  x ← x + η·m/√v̂  (deltas already point downhill).

    fedavg     : x += η Δ
    fedadagrad : v += Δ²                                   (Reddi et al. 2020)
    fedadam    : Adam(m, v)                                (Reddi et al. 2020)
    fedyogi    : Yogi variance update                      (Reddi et al. 2020)
    fedamsgrad : Option 2 — v̂=max(v̂,v),  x += η m/(√v̂+ε)  (Tong et al. 2020)
    fedams     : Option 1 — v̂=max(v̂,v,ε), x += η m/√v̂     (this paper)

Second-moment storage (``FedConfig.server_state_dtype``): v/v̂ may live as
bf16 or int8-blockscale (:class:`QuantState`) — the update math always runs
in fp32 with dequant/requant at the edges, so the quantization error enters
only through the stored state read back next round. The one-pass fused
ingest entry points (:func:`server_ingest_leaf` / :func:`server_ingest` /
:func:`server_ingest_tree`, DESIGN.md §3) consume the compacted
``(vals, idx)`` client selections directly and fold scatter-mean + update +
dequant/requant into a single read-modify-write over the optimizer state —
no dense mean delta is ever materialized.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import FedConfig


class ServerState(NamedTuple):
    m: object       # momentum pytree (zeros for fedavg)
    v: object       # second moment (fp32/bf16 arrays or QuantState)
    vhat: object    # max-stabilized second moment (same storage as v)
    t: jax.Array    # round counter


class QuantState(NamedTuple):
    """int8-blockscale storage for one flat second-moment leaf: ``q`` is
    the (N,) int8 payload over the zero-padded block domain (N = nb·block)
    and ``scale`` the (nb,) fp32 per-block absmax scales — dequant is
    ``q * scale[block]``, requant ``scale = max(|v|)/127`` per block."""
    q: jax.Array
    scale: jax.Array


_is_quant = lambda x: isinstance(x, QuantState)

#: Why hierarchical rounds (``FedConfig.agg_groups > 1``) cannot fuse the
#: server ingest: tier 1 pre-merges each group's selections into a DENSE
#: group partial, so the root consumes g dense partials — there is no
#: compacted (vals, idx) stream left for ``server_ingest`` to scatter.
#: Both backends append this to their ``resolve_fused_ingest`` detail so a
#: forced ``fused_ingest='kernel'/'jnp'`` with groups fails with the same
#: explanation everywhere.
FUSED_INGEST_GROUPS_DETAIL = (
    "; hierarchical aggregation (agg_groups > 1) is also ineligible — the "
    "group tier pre-merges selections into dense partials, leaving no "
    "compacted (vals, idx) stream for the one-pass ingest")


def _dequant_flat(qs: QuantState) -> jax.Array:
    nb = qs.scale.shape[0]
    return (qs.q.astype(jnp.float32).reshape(nb, -1)
            * qs.scale[:, None]).reshape(-1)


def _requant_flat(v, nb: int) -> QuantState:
    vb = v.reshape(nb, -1)
    # absmax as max(max v, -min v): the same float exactly, but avoids
    # materializing a full |v| buffer on backends (CPU XLA) that don't
    # fuse abs into the row reduction
    amax = jnp.maximum(jnp.max(vb, axis=1), -jnp.min(vb, axis=1))
    scale = jnp.maximum(amax / 127.0, 1e-30)
    q = jnp.clip(jnp.round(vb / scale[:, None]), -127, 127).astype(jnp.int8)
    return QuantState(q=q.reshape(-1), scale=scale)


def init_server_state(params, state_dtype: str = "float32",
                      block: int = 2048) -> ServerState:
    """``state_dtype`` selects the v/v̂ storage (m is always fp32); int8
    leaves are stored padded to the ``block`` quantization layout."""
    # m, v, vhat must be DISTINCT buffers: the round executable donates the
    # whole state, and XLA rejects donating one buffer for three parameters
    zeros = lambda: jax.tree.map(
        lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    if state_dtype == "bfloat16":
        second = lambda: jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.bfloat16), params)
    elif state_dtype == "int8":
        from repro.core.compressors import block_layout

        def qleaf(p):
            bs, nb = block_layout(p.size, block)
            return QuantState(q=jnp.zeros(nb * bs, jnp.int8),
                              scale=jnp.full((nb,), 1e-30, jnp.float32))
        second = lambda: jax.tree.map(qleaf, params)
    else:
        second = zeros
    return ServerState(m=zeros(), v=second(), vhat=second(),
                       t=jnp.zeros((), jnp.int32))


def _state_is_quantized(v_tree) -> bool:
    leaves = jax.tree.leaves(v_tree, is_leaf=_is_quant)
    return any(_is_quant(l) or l.dtype != jnp.float32 for l in leaves)


def fedams_step(m, v, vh, d, *, eta: float, beta1: float, beta2: float,
                eps: float, option: int):
    """The FedAMS step on fp32 arrays: ``(increment, m, v, v̂)``. The one
    op sequence every server path runs — jnp, fused ingest and the Pallas
    kernels — so they agree bit for bit across programs and backends:

    * the moments are written ``m + (1-β)·(d - m)``, one product per sum,
      so a compiler that fuses a multiply into the add (XLA:CPU does)
      can only fuse it one way, whichever program it sits in;
      ``β·m + (1-β)·d`` leaves it a choice of two;
    * option 1 scales by ``rsqrt(v̂)``, the form XLA rewrites ``m/√v̂``
      into, so a Pallas kernel (which would divide) matches it;
      option 2 divides by ``√v̂ + ε``."""
    m2 = m + (1 - beta1) * (d - m)
    v2 = v + (1 - beta2) * (jnp.square(d) - v)
    if option == 1:
        vh2 = jnp.maximum(jnp.maximum(vh, v2), eps)
        inc = eta * m2 * lax.rsqrt(vh2)
    else:
        vh2 = jnp.maximum(vh, v2)
        inc = eta * m2 / (jnp.sqrt(vh2) + eps)
    return inc, m2, v2, vh2


def server_update(fed: FedConfig, state: ServerState, params, delta):
    """One server step. Returns (new_params, new_state). Quantized v/v̂
    storage (bf16 arrays or :class:`QuantState` leaves) is dequantized to
    fp32, updated with the exact fp32 math, and requantized — bit-identical
    to the fused ingest's storage round-trip."""
    if _state_is_quantized(state.v):
        return _server_update_quantized(fed, state, params, delta)
    return _server_update_f32(fed, state, params, delta)


def _server_update_quantized(fed: FedConfig, state: ServerState, params,
                             delta):
    if _is_quant(state.v):
        # flat sim leaf: the int8 payload lives on the padded block domain
        # — pad the fp32 streams up, update, slice back
        d = params.size
        N = state.v.q.size
        nb = state.v.scale.shape[0]
        pad = N - d
        padf = lambda a: (jnp.pad(a.reshape(-1).astype(jnp.float32),
                                  (0, pad)) if pad
                          else a.reshape(-1).astype(jnp.float32))
        st = ServerState(m=padf(state.m), v=_dequant_flat(state.v),
                         vhat=_dequant_flat(state.vhat), t=state.t)
        newx, st2 = _server_update_f32(fed, st, padf(params), padf(delta))
        return newx[:d].astype(params.dtype), ServerState(
            m=st2.m[:d], v=_requant_flat(st2.v, nb),
            vhat=_requant_flat(st2.vhat, nb), t=st2.t)
    to32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    st = ServerState(m=state.m, v=to32(state.v), vhat=to32(state.vhat),
                     t=state.t)
    newp, st2 = _server_update_f32(fed, st, params, delta)
    back = lambda new, old: jax.tree.map(
        lambda a, o: a.astype(o.dtype), new, old)
    return newp, ServerState(m=st2.m, v=back(st2.v, state.v),
                             vhat=back(st2.vhat, state.vhat), t=st2.t)


def _server_update_f32(fed: FedConfig, state: ServerState, params, delta):
    algo, b1, b2, eta, eps = fed.algorithm, fed.beta1, fed.beta2, fed.eta, fed.eps
    t = state.t + 1

    if algo == "fedavg":
        new_params = jax.tree.map(
            lambda x, d: x + eta * d.astype(x.dtype), params, delta)
        return new_params, ServerState(state.m, state.v, state.vhat, t)

    if algo in ("fedams", "fedcams", "fedamsgrad"):
        option = _ingest_option(fed)
        out = jax.tree.map(
            lambda mm, vv, vh, d: fedams_step(
                mm, vv, vh, d.astype(jnp.float32), eta=eta, beta1=b1,
                beta2=b2, eps=eps, option=option),
            state.m, state.v, state.vhat, delta)
        is_out = lambda o: isinstance(o, tuple)
        part = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=is_out)
        new_params = jax.tree.map(lambda x, inc: x + inc.astype(x.dtype),
                                  params, part(0))
        return new_params, ServerState(part(1), part(2), part(3), t)

    m = jax.tree.map(lambda mm, d: b1 * mm + (1 - b1) * d.astype(jnp.float32),
                     state.m, delta)

    if algo == "fedyogi":
        def vup(vv, d):
            d2 = jnp.square(d.astype(jnp.float32))
            return vv - (1 - b2) * d2 * jnp.sign(vv - d2)
        v = jax.tree.map(vup, state.v, delta)
    elif algo == "fedadagrad":
        v = jax.tree.map(
            lambda vv, d: vv + jnp.square(d.astype(jnp.float32)),
            state.v, delta)
    else:
        v = jax.tree.map(
            lambda vv, d: b2 * vv + (1 - b2) * jnp.square(d.astype(jnp.float32)),
            state.v, delta)

    if algo not in ("fedadam", "fedyogi", "fedadagrad"):
        raise ValueError(f"unknown algorithm {algo!r}")
    vhat = state.vhat  # unused
    new_params = jax.tree.map(
        lambda x, mm, vv: x + (eta * mm / (jnp.sqrt(vv) + eps)).astype(x.dtype),
        params, m, v)

    return new_params, ServerState(m, v, vhat, t)


# ===========================================================================
# One-pass fused ingest (DESIGN.md §3)
# ===========================================================================


def _ingest_option(fed: FedConfig) -> int:
    """fedamsgrad IS Option 2 regardless of ``fed.option`` — the same
    mapping the jnp ``server_update`` branches implement."""
    return 2 if fed.algorithm == "fedamsgrad" else fed.option


def server_ingest_leaf(fed: FedConfig, x, m, v, vh, vals, idx, n_div, *,
                       block: int, impl: str, interpret=None):
    """One-pass sparse ingest for one padded flat leaf.

    ``x``/``m``: (N,) fp32, N = nb·block (the selection's zero-padded block
    domain); ``v``/``vh``: (N,) fp32/bf16 storage or :class:`QuantState`;
    ``vals``/``idx``: (n, nb·k) client-major gathered selections (global
    indices). ``impl``: ``"kernel"`` (Pallas ``fedams_ingest``) or
    ``"jnp"`` (blocked scatter — the scatter domain is (nb, block), so no
    (N,)-shaped dense delta appears in the jaxpr). Returns
    ``(x2, m2, v2, vh2)`` with state in storage form.

    Numerics: ``"jnp"`` is bitwise identical to the two-pass
    ``server_aggregate_sparse`` + ``server_update`` baseline (the blocked
    (nb, block) scatter-add lowers to the same update sequence as the flat
    one). ``"kernel"`` accumulates collisions per client in a fori_loop —
    bitwise equal to ``fedams_ingest_ref`` but within ≤1 ulp of the
    baseline on coordinates where several clients collide.
    """
    N = x.shape[0]
    nb = N // block
    n = vals.shape[0]
    k = vals.reshape(n, -1).shape[1] // nb
    vals3 = vals.reshape(n, nb, k)
    idx3 = idx.reshape(n, nb, k)
    option = _ingest_option(fed)
    state_dtype = ("int8" if _is_quant(v) else str(jnp.dtype(v.dtype)))

    if impl == "kernel":
        from repro.kernels.fedams_ingest import fedams_ingest
        kw = dict(n_div=n_div, eta=fed.eta, beta1=fed.beta1, beta2=fed.beta2,
                  eps=fed.eps, option=option, block=block,
                  state_dtype=state_dtype, interpret=interpret)
        if state_dtype == "int8":
            x2, m2, qv, qvh, sv, svh = fedams_ingest(
                x, m, v.q, vh.q, vals3, idx3, v.scale, vh.scale, **kw)
            return x2, m2, QuantState(qv, sv.reshape(-1)), QuantState(
                qvh, svh.reshape(-1))
        return fedams_ingest(x, m, v, vh, vals3, idx3, **kw)

    # -- blocked jnp path: scatter-mean on the (nb, block) domain, then the
    # elementwise step per block — the same ops server_update runs flat
    rows = (idx3 // block).reshape(-1)
    cols = (idx3 % block).reshape(-1)
    acc = jnp.zeros((nb, block), jnp.float32).at[rows, cols].add(
        vals3.reshape(-1))
    dm = acc / n_div
    xb, mb = x.reshape(nb, block), m.reshape(nb, block)
    if state_dtype == "int8":
        vv = v.q.astype(jnp.float32).reshape(nb, block) * v.scale[:, None]
        vhd = vh.q.astype(jnp.float32).reshape(nb, block) * vh.scale[:, None]
    else:
        vv = v.astype(jnp.float32).reshape(nb, block)
        vhd = vh.astype(jnp.float32).reshape(nb, block)
    inc, m2, v2, vh2 = fedams_step(mb, vv, vhd, dm, eta=fed.eta,
                                   beta1=fed.beta1, beta2=fed.beta2,
                                   eps=fed.eps, option=option)
    x2 = xb + inc
    if state_dtype == "int8":
        return (x2.reshape(-1), m2.reshape(-1),
                _requant_flat(v2, nb), _requant_flat(vh2, nb))
    if state_dtype == "bfloat16":
        return (x2.reshape(-1), m2.reshape(-1),
                v2.astype(jnp.bfloat16).reshape(-1),
                vh2.astype(jnp.bfloat16).reshape(-1))
    return x2.reshape(-1), m2.reshape(-1), v2.reshape(-1), vh2.reshape(-1)


def server_ingest(fed: FedConfig, state: ServerState, xflat, vals, idx,
                  n_div, *, block: int, impl: str, interpret=None):
    """FedSim entry point: fused ingest on the flat (d,) sim vector.

    ``block`` is the selection block size (``block_layout(d,
    fed.wire_block)[0]``); x/m (and fp32/bf16 v/v̂ storage) are padded to
    the nb·block domain for the pass and sliced back — int8
    :class:`QuantState` leaves already live padded. Returns
    ``(new_flat, new_state)`` exactly like the two-pass
    ``server_aggregate_sparse`` + ``server_update``.
    """
    d = xflat.size
    nb = -(-d // block)
    pad = nb * block - d
    padf = lambda a: jnp.pad(a, (0, pad)) if pad else a
    pad_s = lambda s: s if _is_quant(s) else padf(s)
    unpad_s = lambda s: s if _is_quant(s) else s[:d]
    x2, m2, v2, vh2 = server_ingest_leaf(
        fed, padf(xflat), padf(state.m), pad_s(state.v), pad_s(state.vhat),
        vals, idx, n_div, block=block, impl=impl, interpret=interpret)
    return x2[:d], ServerState(m=m2[:d], v=unpad_s(v2), vhat=unpad_s(vh2),
                               t=state.t + 1)


def server_ingest_tree(fed: FedConfig, st: ServerState, params, sels, n_div,
                       gather, *, block: int, impl: str, interpret=None):
    """Mesh entry point: per-leaf gather + fused ingest over the shard tree.

    ``sels`` has :class:`~repro.core.compressors.Selection` leaves (this
    device's compacted uplink); ``gather`` lifts one (nb·k,) array to the
    gathered (n, nb·k) client-major stack (the client-axis all_gather —
    the identical collective ``stages.sparse_topk_leaf`` runs, so the wire
    payload is unchanged). Returns ``(new_params, new_state)`` like
    ``KernelImpl.fedams_update_tree``. Each leaf's gathers run under the
    ``uplink`` scope and its ingest under ``server_ingest``, apart, as the
    mesh round's layer scopes never nest.
    """
    from repro.core.compressors import Selection, block_layout
    from repro.core.stages import SERVER_INGEST, UPLINK
    is_sel = lambda s: isinstance(s, Selection)
    flat_p, tdef = jax.tree_util.tree_flatten(params)
    flat_m = jax.tree_util.tree_leaves(st.m)
    flat_v = jax.tree_util.tree_leaves(st.v, is_leaf=_is_quant)
    flat_vh = jax.tree_util.tree_leaves(st.vhat, is_leaf=_is_quant)
    flat_s = jax.tree_util.tree_leaves(sels, is_leaf=is_sel)
    xs, ms, vs, vhs = [], [], [], []
    for x, m, v, vh, sel in zip(flat_p, flat_m, flat_v, flat_vh, flat_s):
        bs, nb = block_layout(x.size, block)
        pad = nb * bs - x.size
        padf = lambda a: (jnp.pad(a.reshape(-1).astype(jnp.float32),
                                  (0, pad)) if pad
                          else a.reshape(-1).astype(jnp.float32))
        pads = lambda a: (a if _is_quant(a) else
                          (jnp.pad(a.reshape(-1), (0, pad)) if pad
                           else a.reshape(-1)))
        with jax.named_scope(UPLINK):
            g_vals, g_idx = gather(sel.vals), gather(sel.idx)
        with jax.named_scope(SERVER_INGEST):
            x2, m2, v2, vh2 = server_ingest_leaf(
                fed, padf(x), padf(m), pads(v), pads(vh), g_vals, g_idx,
                n_div, block=bs, impl=impl, interpret=interpret)
            n = x.size
            xs.append(x2[:n].reshape(x.shape).astype(x.dtype))
            ms.append(m2[:n].reshape(x.shape))
            vs.append(v2 if _is_quant(v2) else v2[:n].reshape(x.shape))
            vhs.append(vh2 if _is_quant(vh2) else vh2[:n].reshape(x.shape))
    unf = lambda ls: jax.tree_util.tree_unflatten(tdef, ls)
    return unf(xs), ServerState(m=unf(ms), v=unf(vs), vhat=unf(vhs),
                                t=st.t + 1)
