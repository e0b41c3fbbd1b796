"""The operations and bytes the benchmark divides by: model FLOPs per
round, the bytes the selection and the ingest must move, and the chip's
published peaks (``peaks.json``, keyed by ``device_kind``).

Nothing here reads the program: the counts follow from the configuration's
sizes, the mix and the round's settings.
"""
from __future__ import annotations

import importlib
import json
import os

import correctness
import weights

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def reference_module(cfg: dict):
    return importlib.import_module(f"reference.{cfg['reference']}")


def leaf_sizes(cfg: dict) -> list:
    shapes = weights.shapes(reference_module(cfg).param_defs(cfg))
    out = []
    for s in shapes.values():
        n = 1
        for d in s:
            n *= d
        out.append(n)
    return out


def model_flops_per_round(cfg: dict, mix: dict) -> float:
    """Three forward passes (forward and backward, no recomputation) of
    every token the clients train on in one round."""
    tokens = (mix["batch"] * mix["seq_len"] * mix["local_steps"]
              * mix["clients"])
    fwd = reference_module(cfg).forward_flops_per_token(cfg, mix["seq_len"])
    return 3.0 * fwd * tokens


def padded_blocks(cfg: dict, ratio: float, block: int = 2048):
    """``(padded elements, kept entries)`` of one client's delta over all
    leaves, in the per-leaf block layout."""
    padded = kept = 0
    for n in leaf_sizes(cfg):
        bs, nb = correctness.block_layout(n, block)
        padded += nb * bs
        kept += nb * correctness.keep_per_block(bs, ratio)
    return padded, kept


def select_bytes(cfg: dict, ratio: float) -> float:
    """Least HBM traffic of one client's EF + selection: read the delta and
    the residual, write the residual (4 B each per padded element), write
    k values and k indices per block (4 B each). Its 3 operations an
    element (abs, add, compare) are far below the bf16 peak at this byte
    count, so the bytes bound the time."""
    padded, kept = padded_blocks(cfg, ratio)
    return 12.0 * padded + 8.0 * kept


def ingest_bytes(cfg: dict, ratio: float, clients: int) -> float:
    """Least HBM traffic of the fused ingest and FedAMS step: read and
    write x, m, v and v-hat in float32 (32 B per padded element), and read
    every client's k values and indices per block (8 B an entry)."""
    padded, kept = padded_blocks(cfg, ratio)
    return 32.0 * padded + 8.0 * kept * clients
