"""The command refuses to run without a TPU: it exits non-zero and prints
no result."""
import chipcells  # noqa: F401  (puts the benchmark on the path)
import os
import subprocess
import sys

import manifest


def test_run_exits_nonzero_on_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cell = manifest.manifest()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=manifest.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
