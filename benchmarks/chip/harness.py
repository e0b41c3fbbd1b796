"""One run of one cell.

Set-up (counted in ``setup_s``): the chips, the compile cache, the
program's round for the cell (compiled ahead of the window), the state
made on the device from the seed's weights, the first
``checked_rounds`` rounds driven through the same call and feed as the
window (their losses and state are what the correctness check reads), and
a pool of the window's batches. Then the window: one jitted call per
round, its batch staged and its loss read back, until ``--seconds`` have
passed (``--trace 0``) or for the cell's ``trace_rounds`` under the
profiler (``--trace 1``). After the window the peak memory is read, the
program's state freed, the reference run and the result printed: the
checks as the last lines of standard error, then one JSON line as the last
line of standard output.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import math
import os
import shutil
import statistics
import sys
import time

import correctness
import counts
import manifest
import program
import weights
import xplane
from traffic import Traffic

#: The window cycles through at most this many distinct batches.
POOL = 64


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Readings:
    """What a metric reader (``metrics/<name>.py``) may read."""
    cell: object
    chips: int
    peaks: dict
    setup_s: float
    rounds: int                 # rounds completed in the window
    window_s: float             # first dispatch to last loss read back
    peak_bytes: int
    flops_per_round: float
    select_bytes: float         # one client's least selection traffic
    ingest_bytes: float
    trace: object = None        # xplane.Reduced of a traced window


def chips_for(cell, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's platform is {devices[0].platform!r}")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips, JAX has "
                     f"{len(devices)}")
    return devices[:cell.chips]


class Program:
    """The program's round for one cell, compiled once, with the state,
    feed and readings of each seed's run. ``break_step`` (tests only)
    wraps the compiled round to plant a fault beneath the harness."""

    def __init__(self, cell, devices, break_step=None):
        import jax

        self.cell, self.devices = cell, devices
        self.ref_mod = counts.reference_module(cell.config)
        self.defs = self.ref_mod.param_defs(cell.config)
        self.built = program.build(cell, devices)
        ours, theirs = weights.shapes(self.defs), self.built.param_shapes
        if ours != theirs:
            raise ValueError("the reference's parameters do not match the "
                             "program's: " + str(sorted(
                                 set(ours.items()) ^ set(theirs.items()))))
        self.stage = functools.partial(jax.device_put,
                                       device=self.built.batch_shardings)
        self.break_step = break_step
        self.compiled = self.step = None
        self.refs = {}
        self.norms = jax.jit(weights.leaf_norms)
        self.change = jax.jit(functools.partial(weights.change_norms,
                                                defs=self.defs))

    def start(self, seed: int):
        """``(state, traffic, checked batches)`` of ``seed``'s run; the
        round is compiled at the first call."""
        import jax.numpy as jnp

        key = weights.run_key(seed)
        state = program.make_state(
            self.built, functools.partial(weights.generate, self.defs), key)
        traffic = Traffic(self.cell.traffic, self.cell.config, seed)
        checked = [traffic.round_batch(r)
                   for r in range(self.cell.workload["checked_rounds"])]
        if self.compiled is None:
            self.compiled = self.built.step.lower(
                state, self.stage(checked[0]), jnp.int32(0)).compile()
        self.step = (self.compiled if self.break_step is None
                     else self.break_step(self.compiled))
        return state, traffic, checked

    def round(self, state, r: int, host):
        """One round as the window runs it: stage, dispatch, read the loss."""
        import jax
        import jax.numpy as jnp
        with jax.profiler.TraceAnnotation("stage"):
            batch = self.stage(host)
        with jax.profiler.TraceAnnotation("dispatch"):
            state, met = self.step(state, batch, jnp.int32(r))
        with jax.profiler.TraceAnnotation("sync"):
            loss = float(met["loss"])
        return state, loss

    def checked_rounds(self, state, seed: int, checked):
        """Drive the checked rounds; returns ``(state, readings, seconds of
        each round)``: the losses, the round-0 momentum norms and the
        change of each leaf after the last."""
        prog, took = {"losses": []}, []
        for r, host in enumerate(checked):
            t = time.perf_counter()
            state, loss = self.round(state, r, host)
            took.append(time.perf_counter() - t)
            prog["losses"].append(loss)
            if r == 0:
                prog["m1"] = correctness.to_host(self.norms(state.m))
        prog["change"] = correctness.to_host(
            self.change(state.params, key=weights.run_key(seed)))
        return state, prog, took

    def reference(self, seed: int, checked, traffic, fp8: bool = False):
        """The reference's readings of the same rounds (``fp8``: the
        control's)."""
        from reference.common import Dot
        if fp8 not in self.refs:
            self.refs[fp8] = correctness.Reference(
                self.cell, self.ref_mod, self.defs, Dot(fp8=fp8),
                self.devices)
        return self.refs[fp8].run(weights.run_key(seed), checked, traffic)


def peak_bytes(devices) -> int:
    # the CPU platform (tests) keeps no memory statistics
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             *, require_tpu: bool = True, break_step=None,
             prog_run: Program | None = None) -> dict:
    """One run; returns the result object. Tests pass ``prog_run`` to
    reuse one compiled round over several runs."""
    import jax

    devices = chips_for(cell, require_tpu)
    if require_tpu:
        from repro.launch.cache import enable_compile_cache
        enable_compile_cache()
    if prog_run is None:
        prog_run = Program(cell, devices)
    prog_run.break_step = break_step
    state, traffic, checked = prog_run.start(seed)
    state, prog, took = prog_run.checked_rounds(state, seed, checked)
    n_check = len(checked)
    est = statistics.median(took[1:] or took)
    want = (cell.workload["trace_rounds"] if trace
            else math.ceil(seconds / max(est, 1e-3)) + 2)
    pool = [traffic.round_batch(n_check + i) for i in range(min(want, POOL))]

    trace_dir = os.path.join(manifest.CHECKOUT, ".bench_traces",
                             f"{cell.name}.{seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    attempted = failed = 0
    setup_s = time.perf_counter() - t_start
    if trace:
        scopes = xplane.op_scopes(prog_run.compiled.as_text())
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the host spans need no Python trace
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    while True:
        r = n_check + attempted
        attempted += 1
        try:
            state, loss = prog_run.round(state, r,
                                         pool[(attempted - 1) % len(pool)])
        except Exception as e:  # a round that raises has failed; the
            failed += 1         # donated state is gone, so the window ends
            print(f"round {r} raised: {e!r}", file=sys.stderr)
            break
        failed += not math.isfinite(loss)
        if (attempted >= cell.workload["trace_rounds"] if trace
                else time.perf_counter() - t0 >= seconds):
            break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    t_ref = time.perf_counter()
    peak = peak_bytes(devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    del state, pool
    prog_run.step = None
    gc.collect()

    ref = prog_run.reference(seed, checked, traffic)
    numbers = correctness.compare(prog, ref)
    t_read = time.perf_counter()
    limits = cell.workload["limits"]
    correct = (correctness.verdict(numbers, limits) and failed == 0
               and all(math.isfinite(x) for x in prog["losses"]))

    peaks = counts.peaks(device["kind"]) if require_tpu else {}
    ratio = cell.workload["fed"]["compress_ratio"]
    readings = Readings(
        cell=cell, chips=len(devices), peaks=peaks, setup_s=setup_s,
        rounds=attempted - failed, window_s=window_s, peak_bytes=peak,
        flops_per_round=counts.model_flops_per_round(cell.config,
                                                     cell.traffic),
        select_bytes=counts.select_bytes(cell.config, ratio),
        ingest_bytes=counts.ingest_bytes(cell.config, ratio,
                                         cell.traffic["clients"]))
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed}
    if trace:
        red = xplane.load(trace_dir, len(devices), scopes)
        shutil.rmtree(trace_dir, ignore_errors=True)
        readings.trace = red
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    print(f"timing setup_s {setup_s:.1f} window_s {window_s:.1f} reference_s "
          f"{t_read - t_ref:.1f} reading_s {time.perf_counter() - t_read:.1f}",
          file=sys.stderr)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = manifest.reader(m["name"])(readings)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    checks["failed_rounds"] = {"value": failed, "limit": 0}
    result["checks"] = checks
    return result


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = manifest.cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start)
    except NoChip as e:
        print(f"benchmark: {e}; nothing was run", file=sys.stderr)
        return 3
    print(f"correct {result['correct']}", file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
