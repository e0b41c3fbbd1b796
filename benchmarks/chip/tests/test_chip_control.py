"""The control (the reference with its products on the float8 grid) fails
the comparison that decides ``correct``, at test size."""
import chipcells  # noqa: F401  (puts the benchmark on the path)
import jax
import pytest

import correctness
import harness
from chipcells import tiny_cell
from traffic import Traffic


@pytest.mark.parametrize("name", ["xlstm-350m.k2-s512",
                                  "hubert-xlarge.k1-f250"])
def test_control_is_not_correct(name):
    cell = tiny_cell(name)
    run = harness.Program(cell, jax.devices()[:1])
    seed = 2**31 + 5
    traffic = Traffic(cell.traffic, cell.config, seed)
    checked = [traffic.round_batch(r)
               for r in range(cell.workload["checked_rounds"])]
    ref = run.reference(seed, checked, traffic)
    ctl = run.reference(seed, checked, traffic, fp8=True)
    numbers = correctness.compare(ctl, ref)
    assert not correctness.verdict(numbers, cell.workload["limits"]), numbers
