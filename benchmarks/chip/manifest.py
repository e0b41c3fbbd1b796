"""``BENCHMARK.json`` and the files it names, found by name.

A cell ``<cell>`` of the manifest's ``workloads`` reads
``workloads/<cell>.json`` (the round's settings and the limits of its
correctness check), ``configs/<config>.json`` (the model's sizes) and
``traffic/<traffic>.json`` (the mix). A metric ``<metric>`` is read by
``metrics/<metric>.py``. Adding a cell, a configuration, a mix or a metric
is adding files and entries; no file of the harness names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.abspath(os.path.join(HERE, "..", ".."))
MANIFEST = os.path.join(CHECKOUT, "BENCHMARK.json")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(MANIFEST)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    end_to_end: list
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or manifest()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells are "
                       f"{[w['name'] for w in bench['workloads']]}")
    return Cell(
        name=name, chips=entry["chips"],
        config=load_json(os.path.join(HERE, "configs",
                                      entry["config"] + ".json")),
        traffic=load_json(os.path.join(HERE, "traffic",
                                       entry["traffic"] + ".json")),
        workload=load_json(os.path.join(HERE, "workloads", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def metric_path(name: str) -> str:
    return os.path.join(HERE, "metrics", name + ".py")


def reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    spec = importlib.util.spec_from_file_location(f"metric_{name}",
                                                  metric_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
