"""Cells cut to a size that a test on the CPU can hold, built from the
benchmark's files by name; importing it puts the benchmark's directory on
the path."""
import copy
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import manifest  # noqa: E402

#: Test sizes, per reference family; float32 compute, so the program and
#: the reference agree to rounding and a planted fault is all that differs.
TINY = {
    "xlstm": dict(num_layers=2, d_model=64, vocab_size=256,
                  dtype="float32"),
    "encoder": dict(num_layers=2, d_model=64, d_ff=128, num_heads=4,
                    num_kv_heads=4, vocab_size=32, dtype="float32"),
}

#: (config, traffic, chips) of each workload file; cells that
#: BENCHMARK.json holds are read from it, the others from here.
FILES = {
    "xlstm-350m.k2-s512": ("xlstm-350m", "k2-s512", 1),
    "xlstm-350m.4c-k2-s64": ("xlstm-350m", "4c-k2-s64", 4),
    "hubert-xlarge.k1-f250": ("hubert-xlarge", "k1-f250", 1),
}


def tiny_cell(name: str):
    config, traffic, chips = FILES[name]
    here = manifest.HERE
    bench = manifest.manifest()
    cell = manifest.Cell(
        name=name, chips=chips,
        config=manifest.load_json(os.path.join(here, "configs",
                                               config + ".json")),
        traffic=manifest.load_json(os.path.join(here, "traffic",
                                                traffic + ".json")),
        workload=manifest.load_json(os.path.join(here, "workloads",
                                                 name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if "workloads" not in m],
        per_layer=[m for m in bench["per_layer"] if "workloads" not in m])
    cell.config = {**cell.config, **TINY[cell.config["reference"]]}
    cell.traffic = {**cell.traffic, "batch": 2, "seq_len": 16}
    cell.workload = copy.deepcopy(cell.workload)
    cell.workload["trace_rounds"] = 2
    return cell
