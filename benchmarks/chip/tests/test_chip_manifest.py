"""BENCHMARK.json against the benchmark's contract, and every file it
names found by name."""
import chipcells  # noqa: F401  (puts the benchmark on the path)
import os

import pytest

import manifest

B = manifest.manifest()
METRICS = B["end_to_end"] + B["per_layer"]
CELLS = [w["name"] for w in B["workloads"]]
WHY = 200


def test_top_level_keys_and_command():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "benchmarks/chip/run.py"]
    assert B["paths"] == ["benchmarks/chip"]
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51


def test_names_and_units_use_allowed_characters():
    names = ([c["name"] for c in B["configs"]] + CELLS
             + [m["name"] for m in METRICS]
             + [w["traffic"] for w in B["workloads"]]
             + [k for c in B["configs"] for k in c["reduced"]])
    assert all(manifest.NAME.match(n) for n in names), names
    for group in (B["configs"], B["workloads"], METRICS):
        assert len({x["name"] for x in group}) == len(group)
    assert all(manifest.UNIT.match(m["unit"]) for m in METRICS)
    assert all(m["better"] in ("lower", "higher") for m in METRICS)
    for text in ([c["why"] for c in B["configs"] + B["workloads"]]
                 + [m["layer"] for m in B["per_layer"]]
                 + [c["source"] for c in B["configs"]]):
        assert 1 <= len(text) <= WHY and "\n" not in text and "\t" not in text


def test_entries_have_only_their_keys():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name(name):
    cell = manifest.cell(name)
    entry = next(w for w in B["workloads"] if w["name"] == name)
    conf = next(c for c in B["configs"] if c["name"] == entry["config"])
    assert os.path.isfile(os.path.join(manifest.CHECKOUT, conf["file"]))
    assert cell.config["name"] == entry["config"]
    assert cell.traffic["clients"] == entry["chips"]
    assert set(cell.workload["limits"]) == {"loss_gap", "grad_gap",
                                            "change_gap"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(manifest.reader(m["name"]))


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_reports_what_its_metrics_move(name):
    cell = manifest.cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e


def test_every_config_is_used_and_reductions_name_no_width():
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}
    for c in B["configs"]:
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "d_model", "d_ff"))
        data = manifest.load_json(os.path.join(manifest.CHECKOUT, c["file"]))
        changed = {k for k, v in data.get("published", {}).items()
                   if k in data and data[k] != v}
        assert changed == set(c["reduced"])
