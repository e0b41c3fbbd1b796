"""Readings that the limits of a cell's correctness check are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1-12 \
        [--control 3] [--faults half_batch,loss_altered,no_exchange]

One process, on the chips the cell asks for. For every seed it drives the
program's checked rounds as a run does, frees the program's state, runs the
reference and prints one JSON line with the three compared numbers of the
program (the lower readings). For the first ``--control`` seeds it also runs
the control (the reference with its products on the float8 grid) and, per
``--faults``, the program with that fault planted, and prints their numbers
(the upper readings). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import correctness  # noqa: E402
import faults  # noqa: E402
import harness  # noqa: E402
import manifest  # noqa: E402


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out.extend(range(int(a), int(b) + 1) if b else [int(a)])
    return out


def program_numbers(prog_run, seed):
    state, traffic, checked = prog_run.start(seed)
    state, prog, _ = prog_run.checked_rounds(state, seed, checked)
    del state
    gc.collect()
    return prog, traffic, checked


T0 = time.perf_counter()


def detail(prog, ref) -> dict:
    """Each leaf's gradient and change gaps, for the look behind a limit."""
    grad = correctness.leaf_gaps(prog, ref, "m1", list(ref["m1"]))
    change = correctness.leaf_gaps(prog, ref, "change", list(ref["change"]))
    return {k: [round(grad[k], 6), round(change[k], 6)] for k in grad}


def emit(**kw):
    print(json.dumps({**kw, "at_s": round(time.perf_counter() - T0, 1)}),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    cell = manifest.cell(args.workload)
    devices = harness.chips_for(cell, require_tpu=True)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    run = harness.Program(cell, devices)
    kinds = [f for f in args.faults.split(",") if f]
    for i, seed in enumerate(seeds(args.seeds)):
        prog, traffic, checked = program_numbers(run, seed)
        ref = run.reference(seed, checked, traffic)
        emit(seed=seed, who="program", numbers=correctness.compare(prog, ref),
             losses=prog["losses"], ref_losses=ref["losses"],
             leaves=detail(prog, ref))
        if i >= args.control:
            continue
        ctl = run.reference(seed, checked, traffic, fp8=True)
        emit(seed=seed, who="control", numbers=correctness.compare(ctl, ref),
             losses=ctl["losses"], leaves=detail(ctl, ref))
        for kind in kinds:
            if kind == "no_exchange":
                with faults.no_exchange():
                    broken = harness.Program(cell, devices)
                    p, _, _ = program_numbers(broken, seed)
                del broken
            else:
                run.break_step = faults.wrapper(kind, cell.traffic["clients"])
                p, _, _ = program_numbers(run, seed)
                run.break_step = None
            gc.collect()
            emit(seed=seed, who=kind, numbers=correctness.compare(p, ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
