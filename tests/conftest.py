import json
import os
import subprocess
import sys
import textwrap

# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real device. Multi-device tests funnel through run_forced_devices
# below, which spawns a subprocess that sets
# --xla_force_host_platform_device_count itself (test_sharding.py,
# test_mesh_parity.py).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# tests/ itself, for the optional-dependency shims (hypothesis_fallback)
# and the subprocess-side harness modules (mesh_parity_harness)
sys.path.insert(0, os.path.dirname(__file__))

import jax
import pytest

jax.config.update("jax_platform_name", "cpu")

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_forced_devices(code: str, devices: int = 8,
                       timeout: int = 1200) -> str:
    """Run ``code`` in a subprocess seeing ``devices`` fake host CPU
    devices (the in-process suite must keep seeing ONE device — see the
    note at the top of this file). ``src/`` and ``tests/`` are both on the
    subprocess PYTHONPATH, so harness modules that live next to the tests
    (e.g. ``mesh_parity_harness``) import directly. Returns stdout;
    asserts a zero exit with the subprocess stderr tail on failure.

    This is the reusable differential-harness entry point: parametrized
    config grid → paired runs inside ONE subprocess (shared jax init, same
    process so same XLA codegen for both sides of every pair) → the
    subprocess prints a JSON summary consumed via
    :func:`forced_devices_json`."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC, os.path.dirname(__file__)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def forced_devices_json(code: str, devices: int = 8, timeout: int = 1200):
    """:func:`run_forced_devices`, parsing the subprocess's last stdout
    line as JSON (the paired-run summary)."""
    return json.loads(run_forced_devices(code, devices, timeout)
                      .strip().splitlines()[-1])


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def tiny_dense_cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="tiny", family="dense", num_layers=2, d_model=32,
                       num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                       dtype="float32")
