"""Compile the mesh round of a tiny dense LM on each aggregation path and
list its instructions with the layer scope path XLA kept for each.

Imported by ``tests/test_layer_scopes.py`` in process (one device) and in
a subprocess with four forced host devices (never collected by pytest).
The scope path of an instruction is its ``op_name``, read with the
benchmark's own reader (``benchmarks/chip/xplane.op_scopes``), so the
tests hold the program to what the trace reduction matches.
"""
from __future__ import annotations

import collections
import contextlib
import importlib.util
import os
import re
import sys
from unittest import mock

XPLANE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                      "benchmarks", "chip", "xplane.py")

#: name -> (FedConfig kwargs, whether the Pallas kernels run, interpreted);
#: ``agg_groups`` 2 splits the client axis into groups and members;
#: ``fault`` holds ``FaultConfig`` kwargs, and such a case runs on a mesh of
#: client axes alone, since the validated round does not yet trace with a
#: ``model`` axis.
CASES = {
    "fused": (dict(compressor="blocktopk", aggregation="sparse",
                   fused_ingest="jnp", mesh_sparse_impl="jnp"), False),
    "fused-kernel": (dict(compressor="blocktopk", aggregation="sparse",
                          fused_ingest="kernel", mesh_sparse_impl="kernel"),
                     True),
    "sparse": (dict(compressor="blocktopk", aggregation="sparse",
                    fused_ingest="off", mesh_sparse_impl="jnp"), False),
    "dense": (dict(compressor="topk", aggregation="dense"), False),
    "packedsign": (dict(compressor="packedsign", aggregation="sparse"),
                   False),
    "grouped": (dict(compressor="blocktopk", aggregation="sparse",
                     mesh_sparse_impl="jnp", agg_groups=2), False),
    "validated": (dict(compressor="blocktopk", aggregation="sparse",
                       fused_ingest="off", mesh_sparse_impl="jnp",
                       track_gamma=False, fault=dict(corrupt_prob=0.5)),
                  False),
}

#: ``%name = <shape> <opcode>(<rest>``: the shape may be a tuple with
#: spaces.
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?)\s([a-z][\w\-]*)"
                   r"\((.*)$", re.M)
GROUP = re.compile(r"replica_groups=\{\{([\d,]*)\}")


def _xplane():
    spec = importlib.util.spec_from_file_location("scope_xplane", XPLANE)
    mod = sys.modules.setdefault(spec.name,
                                 importlib.util.module_from_spec(spec))
    spec.loader.exec_module(mod)
    return mod


class _ClientAxesOnly:
    """``model`` with every ``model`` mesh axis taken out of its specs."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    @staticmethod
    def _drop(defs):
        import dataclasses

        import jax
        from jax.sharding import PartitionSpec as P

        from repro.models import params as pdefs
        return jax.tree.map(
            lambda d: dataclasses.replace(d, spec=P(*(
                None if a == "model" else a for a in d.spec))),
            defs, is_leaf=pdefs.is_def)

    def defs(self):
        return self._drop(self._model.defs())

    def train_batch_defs(self, global_batch, seq_len):
        return self._drop(self._model.train_batch_defs(global_batch,
                                                       seq_len))


def compiled_text(case: str, devices: int) -> str:
    """The optimized HLO of ``case``'s round on the first ``devices``
    devices, one client per device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.comm.faults import FaultConfig
    from repro.configs import FedConfig, TrainConfig
    from repro.configs.base import ModelConfig
    from repro.core.mesh import fed_batch_defs, fed_state_defs, jit_fed_round
    from repro.kernels.ops import KernelImpl
    from repro.models import params as pdefs
    from repro.models.model import Model

    fed_kw, kernels = CASES[case]
    fed_kw = dict(fed_kw)
    fault = fed_kw.pop("fault", None)
    groups = fed_kw.get("agg_groups", 1)
    axes = (("cgroup", "data") if groups > 1
            else ("data",) if devices > 1 else ())
    cfg = ModelConfig(name="tiny", family="dense", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      dtype="float32")
    model = Model(cfg)
    if fault is not None:
        model, fed_kw["fault"] = _ClientAxesOnly(model), FaultConfig(**fault)
    fed = FedConfig(algorithm="fedcams", compress_ratio=1 / 16,
                    local_steps=2, num_clients=devices,
                    client_axes=axes, **fed_kw)
    train = TrainConfig(global_batch=2 * devices, seq_len=8, rounds=1)
    shape = (groups, devices // groups, 1) if groups > 1 else (devices, 1)
    if fault is not None:
        shape = shape[:-1]
    names = ("cgroup", "data", "model")[-len(shape) - (fault is not None):]
    mesh = Mesh(np.array(jax.devices()[:devices]).reshape(shape),
                names[:len(shape)])
    step = jit_fed_round(model, fed, train, mesh,
                         kernel_impl=KernelImpl(interpret=True)
                         if kernels else None)
    struct = lambda defs: jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype),
                                       sharding=NamedSharding(mesh, d.spec)),
        defs, is_leaf=pdefs.is_def)
    seed = jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    return step.lower(struct(fed_state_defs(model, fed)),
                      struct(fed_batch_defs(model, fed, train)),
                      seed).compile().as_text()


def instructions(hlo: str, scopes: dict) -> list:
    """``[opcode, shape, op_name, devices]`` of every instruction of
    ``hlo``: ``op_name`` "" where XLA kept none, ``devices`` the size of a
    collective's first replica group (1 for every other instruction)."""
    out = []
    for name, shape, op, rest in INSTR.findall(hlo):
        group = GROUP.search(rest)
        out.append([op, shape, scopes.get(name, ""),
                    len(group.group(1).split(",")) if group else 1])
    return out


def table(case: str, devices: int) -> dict:
    """``case``'s instructions, and the (opcode, shape) multisets that the
    round's instructions differ by when ``jax.named_scope`` names
    nothing."""
    import jax
    scoped = compiled_text(case, devices)
    with mock.patch.object(jax, "named_scope",
                           lambda name: contextlib.nullcontext()):
        plain = compiled_text(case, devices)
    count = lambda hlo: collections.Counter(
        (op, shape) for _, shape, op, _ in INSTR.findall(hlo))
    a, b = count(scoped), count(plain)
    return {"instrs": instructions(scoped, _xplane().op_scopes(scoped)),
            "only_scoped": sorted(map(list, (a - b).elements())),
            "only_plain": sorted(map(list, (b - a).elements()))}


def main(cases, devices: int) -> dict:
    return {case: table(case, devices) for case in cases}
