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


@pytest.mark.parametrize("rounds,extra,traced", [
    ("3", ["--trace-rounds", "2"], 2),
    ("4", ["--scan-rounds", "2", "--trace-rounds", "1"], 1)],
    ids=["per-round", "scan"])
def test_train_main_traces_its_rounds_host_spans(tmp_path, rounds, extra,
                                                 traced):
    """``--trace-dir`` writes a profiler trace of steps 1..--trace-rounds
    (rounds, or scanned chunks of rounds): each traced step is one
    ``round`` holding one ``stage``, one ``dispatch`` and one ``sync``
    span, the names the benchmark's trace reduction reads."""
    import collections
    import glob
    import os
    from repro.launch.train import main
    main(ARGS + PROVIDERS["jnp"] + ["--rounds", rounds,
                                    "--trace-dir", str(tmp_path)] + extra)
    files = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    names = collections.Counter(
        e.name for plane in jax.profiler.ProfileData.from_file(files[0]).planes
        if not plane.name.startswith("/device:")
        for line in plane.lines for e in line.events)
    spans = ("round", "stage", "dispatch", "sync")
    assert {n: names[n] for n in spans} == dict.fromkeys(spans, traced)
