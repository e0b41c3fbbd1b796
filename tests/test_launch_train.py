"""``python -m repro.launch.train`` end to end at smoke size on the CPU: the
normal entry point traces, trains and returns, with one client (dp=1) and
two (dp=2 on virtual devices), through the jnp providers and the Pallas
kernels (interpret mode here)."""
import math

import jax
import numpy as np
import pytest

from conftest import forced_devices_json

ARGS = ["--arch", "xlstm-350m", "--smoke", "--rounds", "2",
        "--aggregation", "sparse", "--compressor", "blocktopk",
        "--local-steps", "2", "--global-batch", "4", "--seq-len", "16"]
PROVIDERS = {"jnp": ["--mesh-sparse-impl", "jnp", "--fused-ingest", "jnp"],
             "kernel": ["--mesh-sparse-impl", "kernel",
                        "--fused-ingest", "kernel"]}


def _leaves(state):
    return [np.asarray(x) for x in jax.tree.leaves(state)]


@pytest.mark.parametrize("impl", list(PROVIDERS))
def test_train_main_one_client(impl):
    from repro.launch.train import main
    from repro.models import params as pdefs
    from repro.configs.registry import get_arch
    from repro.models.model import Model
    out = main(ARGS + PROVIDERS[impl])
    losses = [h["loss"] for h in out["history"]]
    assert len(losses) == 2 and all(math.isfinite(v) for v in losses)
    assert out["kernels"] == ("interpret" if impl == "kernel" else "off")
    init = pdefs.init_params(Model(get_arch("xlstm-350m").smoke).defs(),
                             jax.random.PRNGKey(0))
    moved = [not np.array_equal(a, b) for a, b in
             zip(_leaves(out["state"].params), _leaves(init))]
    assert any(moved)


@pytest.mark.parametrize("impl", list(PROVIDERS))
def test_train_main_two_clients(impl):
    out = forced_devices_json(f"""
        import json, math
        from repro.launch.train import main
        out = main({ARGS + ["--dp", "2"] + PROVIDERS[impl]!r})
        print(json.dumps([h["loss"] for h in out["history"]]))
    """, devices=2, timeout=900)
    assert len(out) == 2 and all(math.isfinite(v) for v in out)


def test_train_main_kernel_round_equals_jnp_round():
    """One round from the same init and batch: the kernel providers and
    the jnp providers leave the whole federated state bit for bit equal
    (selection and one-client ingest are exact contracts)."""
    from repro.launch.train import main
    one = [a if a != "2" else "1" for a in ARGS]
    a = main(one + PROVIDERS["kernel"])
    b = main(one + PROVIDERS["jnp"])
    assert a["history"][0]["loss"] == b["history"][0]["loss"]
    for x, y in zip(_leaves(a["state"]), _leaves(b["state"])):
        assert np.array_equal(x, y)
