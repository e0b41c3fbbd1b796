"""The readers of the program's layer scopes on hand-made windows:
``local_train_ms`` and ``unscoped_ms``, and the layer metrics together
summing to the busy time of a round whose operations are scoped
disjointly."""
import chipcells  # noqa: F401  (puts the benchmark on the path)
import types

import pytest

import manifest
from xplane import Op, Reduced

ROUNDS = 2


def readings(chips):
    red = Reduced(chips, [("dispatch", 0, 1000)], (0, 1000))
    return types.SimpleNamespace(trace=red, rounds=ROUNDS)


def read(name, r):
    return manifest.reader(name)(r)


def scoped_round(offset=0):
    """One chip's round: every layer, a kernel matched by name inside its
    scope, and an operation under none; no two operations overlap."""
    at = lambda a, b: (offset + a, offset + b)
    return [Op("fusion", *at(0, 40), "jit(r)/local_train/while/body/dot"),
            Op("convert", *at(40, 50), "jit(r)/local_train/sub"),
            Op("reshape", *at(50, 60), "jit(r)/ef_select/reshape"),
            Op("topk_ef_sparse", *at(60, 160), "jit(r)/ef_select/pallas"),
            Op("all-gather", *at(160, 165), "jit(r)/uplink/all_gather"),
            Op("fedams_ingest", *at(170, 200), "jit(r)/server_ingest/p"),
            Op("reshape", *at(200, 215), "jit(r)/server_ingest/reshape"),
            Op("xor", *at(220, 223), "jit(r)/threefry2x32"),
            Op("copy-done", *at(223, 230), "")]


def test_local_train_ms_sums_its_scope_per_round():
    r = readings({0: scoped_round()})
    assert read("local_train_ms", r) == pytest.approx(50 / 1e6 / ROUNDS)


def test_local_train_ms_averages_over_chips():
    r = readings({0: scoped_round(), 1: scoped_round()[:1]})
    assert read("local_train_ms", r) == pytest.approx(
        (50 + 40) / 2 / 1e6 / ROUNDS)


def test_unscoped_ms_counts_operations_under_no_layer():
    r = readings({0: scoped_round()})
    assert read("unscoped_ms", r) == pytest.approx(10 / 1e6 / ROUNDS)


def test_layer_readers_read_nothing_without_the_layer_map():
    """A program without the scopes (as before they were added): the
    kernels are still found by name, the scope readers read nothing."""
    plain = [Op(o.name, o.start, o.end, "jit(r)/" + o.name)
             for o in scoped_round()]
    r = readings({0: plain})
    assert read("local_train_ms", r) is None
    assert read("unscoped_ms", r) is None
    assert read("select_ms", r) == pytest.approx(100 / 1e6 / ROUNDS)
    assert read("ingest_ms", r) == pytest.approx(30 / 1e6 / ROUNDS)


def test_no_trace_or_no_chip_reads_nothing():
    for name in ("local_train_ms", "unscoped_ms"):
        assert read(name, types.SimpleNamespace(trace=None, rounds=2)) is None
        assert read(name, readings({})) is None


@pytest.mark.parametrize("chips", [1, 2])
def test_layers_and_unscoped_sum_to_busy_time(chips):
    """Disjoint scopes: selection (kernel and its reshapes), ingest,
    local training, the uplink and what lies under none add up to the busy
    time a round, since each operation is counted once."""
    r = readings({c: scoped_round(offset=c) for c in range(chips)})
    uplink = sum(r.trace.sum_s(c, r"(?!)", "uplink")
                 for c in r.trace.chips) / chips * 1e3 / ROUNDS
    assert read("select_ms", r) == pytest.approx(110 / 1e6 / ROUNDS)
    assert read("ingest_ms", r) == pytest.approx(45 / 1e6 / ROUNDS)
    total = sum(read(m, r) for m in ("select_ms", "ingest_ms",
                                     "local_train_ms", "unscoped_ms"))
    busy_ms = r.trace.busy_s() * 1e3 / ROUNDS
    assert total + uplink == pytest.approx(busy_ms, rel=1e-12)
