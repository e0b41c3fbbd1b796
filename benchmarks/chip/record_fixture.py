"""Record the small four-chip trace that the trace reduction's tests read.

    python3 benchmarks/chip/record_fixture.py <output.xplane.pb>

On four TPU chips: each chip selects the top 32 of every block of 2048
with the program's ``topk_ef_sparse`` kernel, the selections are gathered
across the chips, and a reduction runs beside the gather; two steps are
traced between the harness's host spans. The trace is copied to the given
path and its expected readings are printed as JSON.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import program  # noqa: E402,F401  (puts the program on the path)
import xplane  # noqa: E402


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.kernels.topk_ef import topk_ef_sparse

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < 4:
        print("record_fixture: needs four TPU chips", file=sys.stderr)
        return 3
    mesh = Mesh(devices[:4], ("c",))
    n = 2048 * 256

    def body(x, e):
        vals, idx, ne = topk_ef_sparse(x, e, k=32, block=2048)
        g = jax.lax.all_gather(vals, "c", tiled=True)
        return (jnp.sum(g) + jnp.sum(ne * ne))[None], idx

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("c"), P("c")),
                              out_specs=(P("c"), P("c")), check_vma=False))
    sh = NamedSharding(mesh, P("c"))
    x = jax.device_put(jax.random.normal(jax.random.PRNGKey(0), (4 * n,)), sh)
    e = jax.device_put(jnp.zeros((4 * n,)), sh)
    jax.block_until_ready(f(x, e))
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("stage"):
            xs = x * 1.0
        with jax.profiler.TraceAnnotation("dispatch"):
            s, _ = f(xs, e)
        with jax.profiler.TraceAnnotation("sync"):
            float(s[0])
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(tmp)
    red = xplane.load(os.path.dirname(os.path.abspath(out)), 4)
    print(json.dumps({
        "window_s": red.window_s, "busy_s": red.busy_s(),
        "select_s": [red.sum_s(c, r"^topk_ef_sparse$") for c in range(4)],
        "gather_s": [red.sum_s(c, r"^all-gather", with_async=True)
                     for c in range(4)],
        "exposed_s": [red.exposed_s(c, r"^all-gather", with_async=True)
                      for c in range(4)],
        "names": sorted({o.name for o in red.chips[0]}),
        "async_names": sorted({o.name for o in red.asyncs.get(0, [])}),
        "breakdown": red.breakdown()}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
