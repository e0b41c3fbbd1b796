"""The system under test, as the benchmark drives it.

This is the only module of the benchmark that imports the program
(``repro``). It builds the program's objects for one cell from the cell's
files and hands back the round entry ``repro.core.mesh.jit_fed_round``,
the state layout and the batch layout. Everything that judges the program
(weights, traffic, reference, counts, trace reduction) lives elsewhere in
this directory and never imports ``repro``.
"""
from __future__ import annotations

import dataclasses
import os
import sys

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
SRC = os.path.join(CHECKOUT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

#: Keys of a configuration file that describe it and are not model fields.
META_KEYS = ("name", "source", "reference", "published", "assumed",
             "deployment", "departures")


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, XLSTMConfig
    fields = {k: v for k, v in cfg.items() if k not in META_KEYS}
    fields["name"] = cfg["name"]
    if "block_pattern" in fields:
        fields["block_pattern"] = tuple(fields["block_pattern"])
    if "xlstm" in fields:
        x = dict(fields["xlstm"])
        x["pattern"] = tuple(x["pattern"])
        fields["xlstm"] = XLSTMConfig(**x)
    return ModelConfig(**fields)


def fed_config(fed: dict, clients: int, local_steps: int):
    from repro.configs import FedConfig
    return FedConfig(num_clients=clients, local_steps=local_steps,
                     client_axes=("data",) if clients > 1 else (), **fed)


def make_mesh(devices):
    """One client per chip: the ``data`` axis enumerates the clients and
    the ``model`` axis has size 1, as ``launch.train --dp n`` lays it out."""
    import jax
    import numpy as np
    auto = jax.sharding.AxisType.Auto
    return jax.sharding.Mesh(np.array(devices).reshape(len(devices), 1),
                             ("data", "model"), axis_types=(auto, auto))


@dataclasses.dataclass
class Built:
    """The program's objects for one cell."""
    model: object
    fed: object
    train: object
    mesh: object
    kernel_impl: object
    step: object            # jit_fed_round: (state, batch, seed) -> ...
    state_shardings: object
    batch_shardings: dict
    param_shapes: dict      # flat path -> shape, from the program's defs


def build(cell, devices, *, kernels=None) -> Built:
    """The round of ``cell`` on ``devices`` through the program's own
    ``jit_fed_round``. ``kernels`` None takes ``default_kernel_impl()``:
    the compiled Pallas kernels on TPU, as ``launch.train`` runs them."""
    import jax
    from jax.sharding import NamedSharding
    from repro.configs import TrainConfig
    from repro.core.mesh import fed_batch_defs, fed_state_defs, jit_fed_round
    from repro.kernels.ops import default_kernel_impl
    from repro.models import params as pdefs
    from repro.models.model import Model

    mix = cell.traffic
    clients = mix["clients"]
    model = Model(model_config(cell.config))
    fed = fed_config(cell.workload["fed"], clients, mix["local_steps"])
    train = TrainConfig(global_batch=mix["batch"] * clients,
                        seq_len=mix["seq_len"], rounds=1,
                        remat_policy=cell.workload.get("remat_policy",
                                                       "none"))
    mesh = make_mesh(devices)
    kernel_impl = default_kernel_impl() if kernels is None else kernels
    step = jit_fed_round(model, fed, train, mesh, kernel_impl=kernel_impl)
    shard = lambda defs: jax.tree.map(
        lambda d: NamedSharding(mesh, d.spec), defs, is_leaf=pdefs.is_def)
    flat, _ = jax.tree_util.tree_flatten_with_path(model.defs(),
                                                   is_leaf=pdefs.is_def)
    shapes = {jax.tree_util.keystr(p): tuple(d.shape) for p, d in flat}
    return Built(model=model, fed=fed, train=train, mesh=mesh,
                 kernel_impl=kernel_impl, step=step,
                 state_shardings=shard(fed_state_defs(model, fed)),
                 batch_shardings=shard(fed_batch_defs(model, fed, train)),
                 param_shapes=shapes)


def state_struct(built: Built):
    """ShapeDtypeStructs of the round's state, with their shardings."""
    import jax
    import jax.numpy as jnp
    from repro.core.mesh import fed_state_defs
    from repro.models import params as pdefs
    defs = fed_state_defs(built.model, built.fed)
    return jax.tree.map(
        lambda d, s: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype),
                                          sharding=s),
        defs, built.state_shardings, is_leaf=pdefs.is_def)


def batch_struct(built: Built):
    import jax
    import jax.numpy as jnp
    from repro.core.mesh import fed_batch_defs
    from repro.models import params as pdefs
    defs = fed_batch_defs(built.model, built.fed, built.train)
    return jax.tree.map(
        lambda d, s: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype),
                                          sharding=s),
        defs, built.batch_shardings, is_leaf=pdefs.is_def)


def make_state(built: Built, make_params, key):
    """The round-0 federated state, made on the device in one jitted call:
    ``make_params(key)`` (the benchmark's seeded weights, float32) and
    zeros for the server moments and the clients' error-feedback rows, each
    leaf born with the program's own state sharding. The key is an argument,
    so every seed runs the one compiled program."""
    import jax
    import jax.numpy as jnp
    from repro.core.mesh import FedMeshState
    struct = state_struct(built)

    def init(key):
        zeros = lambda t: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), t)
        return FedMeshState(params=make_params(key), m=zeros(struct.m),
                            v=zeros(struct.v), vhat=zeros(struct.vhat),
                            errors=zeros(struct.errors),
                            round=jnp.zeros((), jnp.int32))

    return jax.jit(init, out_shardings=built.state_shardings)(key)
