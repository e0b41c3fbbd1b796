"""The four-client cell on four CPU devices: a sound run is correct, and
with the exchange between chips left out it is not."""
import chipcells  # noqa: F401  (puts the benchmark on the path)
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def test_four_clients_sound_and_without_exchange():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, os.path.join(HERE, "mesh_child.py")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"]["count"] == 4
    assert out["sound"]["correct"], out["sound"]["checks"]
    assert not out["no_exchange"]["correct"], out["no_exchange"]["checks"]
