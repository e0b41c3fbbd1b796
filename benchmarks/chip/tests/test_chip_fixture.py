"""The trace reduction on a small trace recorded on four v5e chips
(``record_fixture.py``): busy union, kernel sums and the exposed part of
the all-gather, against the readings taken when it was recorded."""
import chipcells  # noqa: F401  (puts the benchmark on the path)
import json
import os

import pytest

import xplane

FIX = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "fixtures")


@pytest.fixture(scope="module")
def red():
    return xplane.load(FIX, 4)


@pytest.fixture(scope="module")
def want():
    with open(os.path.join(FIX, "four_chips.json")) as f:
        return json.load(f)


def test_four_chips_and_a_window(red, want):
    assert sorted(red.chips) == [0, 1, 2, 3]
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-12)
    assert 0 < red.busy_s() <= red.window_s
    assert red.busy_s() == pytest.approx(want["busy_s"], rel=1e-12)


def test_kernel_and_collective_sums(red, want):
    for c in range(4):
        sel = red.sum_s(c, r"^topk_ef_sparse$")
        gat = red.sum_s(c, r"^all-gather", with_async=True)
        exp = red.exposed_s(c, r"^all-gather", with_async=True)
        assert sel > 0 and gat > 0
        assert 0 <= exp <= gat
        assert sel == pytest.approx(want["select_s"][c], rel=1e-12)
        assert gat == pytest.approx(want["gather_s"][c], rel=1e-12)
        assert exp == pytest.approx(want["exposed_s"][c], rel=1e-12)


def test_breakdown_of_the_recorded_trace(red, want):
    bd = red.breakdown()
    assert bd == json.loads(json.dumps(want["breakdown"]))
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert {name for name, _ in bd["idle_gaps"]} <= {"stage", "dispatch",
                                                     "sync", "host"}
