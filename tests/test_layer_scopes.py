"""The mesh round's layer scopes in its compiled program, on each path of
its aggregation: every instruction sits under at most one of
``local_train``, ``ef_select``, ``uplink`` and ``server_ingest``; the
matmuls are local training, the client-axis collectives are the uplink,
the aggregate's scatter and the adaptive step are the server ingest; and
the scopes change no compiled instruction (they are metadata)."""
import functools

import pytest

import scope_harness
from conftest import forced_devices_json
from repro.core.stages import (LAYER_SCOPES, LOCAL_TRAIN, SERVER_INGEST,
                               UPLINK)

ONE = ["fused", "fused-kernel"]
FOUR = ["fused", "sparse", "dense", "packedsign", "grouped", "validated"]
PATHS = [(c, 1) for c in ONE] + [(c, 4) for c in FOUR]


@functools.cache
def _one_device(case):
    return scope_harness.table(case, 1)


@pytest.fixture(scope="module")
def four_devices():
    return forced_devices_json(f"""
        import json
        import scope_harness
        print(json.dumps(scope_harness.main({FOUR!r}, 4)))
    """, devices=4, timeout=900)


@pytest.fixture
def table(request):
    case, devices = request.param
    if devices == 1:
        return _one_device(case)
    return request.getfixturevalue("four_devices")[case]


def layers(op_name):
    return [s for s in LAYER_SCOPES if s in op_name]


paths = pytest.mark.parametrize("table", PATHS, indirect=True,
                                ids=[f"{c}-{d}dev" for c, d in PATHS])


@paths
def test_no_instruction_sits_under_two_layers(table):
    both = [i for i in table["instrs"] if len(layers(i[2])) > 1]
    assert not both, both[:5]
    for scope in (LOCAL_TRAIN, SERVER_INGEST):
        assert any(layers(i[2]) == [scope] for i in table["instrs"]), scope


@paths
def test_matmuls_are_local_training(table):
    mm = [i for i in table["instrs"] if i[0] in ("dot", "convolution")]
    assert mm
    assert all(layers(i[2]) == [LOCAL_TRAIN] for i in mm), \
        [i for i in mm if layers(i[2]) != [LOCAL_TRAIN]][:5]


@paths
def test_client_axis_collectives_are_the_uplink(table, request):
    """A collective over more than one device crosses the client axis
    (the model axis has one device here). Each sits under ``uplink``; the
    loss's scalar mean may stay outside every layer."""
    across = [i for i in table["instrs"]
              if i[0].startswith(("all-gather", "all-reduce")) and i[3] > 1]
    stray = [i for i in across if layers(i[2]) != [UPLINK]
             and not (i[0].startswith("all-reduce") and i[1] == "f32[]"
                      and not layers(i[2]))]
    assert not stray, stray[:5]
    _, devices = request.node.callspec.params["table"]
    assert (devices > 1) == any(layers(i[2]) == [UPLINK] for i in across)


@paths
def test_aggregate_and_adaptive_step_are_the_server_ingest(table):
    """The aggregate's scatter-add and the FedAMS step's ``rsqrt`` outside
    local training sit under ``server_ingest``."""
    server = [i for i in table["instrs"]
              if i[2].endswith(("/scatter-add", "/rsqrt"))
              and LOCAL_TRAIN not in i[2]]
    assert any(i[2].endswith("/rsqrt") for i in server)
    assert all(layers(i[2]) == [SERVER_INGEST] for i in server), \
        [i for i in server if layers(i[2]) != [SERVER_INGEST]][:5]


@paths
def test_scopes_change_no_compiled_instruction(table):
    assert table["only_scoped"] == [] and table["only_plain"] == []
