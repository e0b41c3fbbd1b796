"""Run in a child process with four CPU devices (see test_chip_mesh.py):
the four-client cell at test size, sound and with the exchange between
chips left out; prints one JSON line of both verdicts."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipcells import tiny_cell  # noqa: E402  (puts the benchmark on the path)
import faults  # noqa: E402
import harness  # noqa: E402


def verdict(fault: bool) -> dict:
    cell = tiny_cell("xlstm-350m.4c-k2-s64")
    if fault:
        with faults.no_exchange():
            import jax
            run = harness.Program(cell, jax.devices()[:4])
            run.start(7)
    else:
        run = None
    res = harness.run_cell(cell, 7, 0.3, False, time.perf_counter(),
                           require_tpu=False, prog_run=run)
    return {"correct": res["correct"], "checks": res["checks"],
            "count": res["device"]["count"]}


if __name__ == "__main__":
    print(json.dumps({"sound": verdict(False), "no_exchange": verdict(True)}))
