"""The harness end to end on the CPU at test size: a sound run is correct,
and each fault planted beneath it makes ``correct`` come out false."""
import chipcells  # noqa: F401  (puts the benchmark on the path)
import time

import jax
import pytest

import faults
import harness
from chipcells import tiny_cell

SEED = 2**31 + 12345
LM = "xlstm-350m.k2-s512"


@pytest.fixture(scope="module")
def lm():
    cell = tiny_cell(LM)
    return cell, harness.Program(cell, jax.devices()[:1])


def run(cell, prog_run=None, break_step=None, trace=False):
    return harness.run_cell(cell, SEED, 0.5, trace, time.perf_counter(),
                            require_tpu=False, break_step=break_step,
                            prog_run=prog_run)


def test_sound_run_is_correct(lm):
    res = run(*lm)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"round_s", "peak_hbm_gb", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("kind", ["unchanged", "half_batch", "loss_altered"])
def test_planted_fault_is_not_correct(lm, kind):
    cell, prog_run = lm
    res = run(cell, prog_run, faults.wrapper(kind, cell.traffic["clients"]))
    assert not res["correct"], res["checks"]


def test_traced_encoder_run_reads_its_window():
    res = run(tiny_cell("hubert-xlarge.k1-f250"), trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 2
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
