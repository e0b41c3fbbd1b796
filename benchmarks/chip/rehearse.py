"""Compile each cell's round for a described TPU v5e, with no chip attached,
and print the compiler's memory analysis per device.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [cell ...]

One-chip cells compile for one device of a described ``v5e:2x2``; a
four-chip cell for all four. The round is the one the benchmark drives
(``jit_fed_round`` with the compiled kernels), lowered from shapes alone.
Run it by hand before a chip call: what the chip's compiler refuses, or a
round that does not fit, shows here at no chip time. A compile that passes
is not a chip run, and its numbers are the compiler's estimate.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

GB = 1e9
HBM_V5E = 16e9


def rehearse(name: str) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    import manifest
    import program
    from repro.kernels.ops import KernelImpl

    cell = manifest.cell(name)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    built = program.build(cell, topo.devices[:cell.chips],
                          kernels=KernelImpl(interpret=False))
    t0 = time.perf_counter()
    lowered = built.step.lower(program.state_struct(built),
                               program.batch_struct(built),
                               jax.ShapeDtypeStruct((), jnp.int32))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    out = {
        "cell": name, "compile_s": time.perf_counter() - t0,
        "argument_gb": mem.argument_size_in_bytes / GB,
        "output_gb": mem.output_size_in_bytes / GB,
        "alias_gb": mem.alias_size_in_bytes / GB,
        "temp_gb": mem.temp_size_in_bytes / GB,
        "kernels": text.count("tpu_custom_call"),
        "all_gathers": text.count("all-gather"),
    }
    out["peak_gb"] = (out["argument_gb"] + out["output_gb"]
                      - out["alias_gb"] + out["temp_gb"])
    out["spare_gb"] = HBM_V5E / GB - out["peak_gb"]
    return out


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    import manifest
    names = (argv or sys.argv[1:]) or [w["name"] for w in
                                       manifest.manifest()["workloads"]]
    for name in names:
        r = rehearse(name)
        print(" ".join(f"{k}={v:.3f}" if isinstance(v, float) else
                       f"{k}={v}" for k, v in r.items()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
