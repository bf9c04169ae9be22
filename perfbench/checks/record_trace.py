#!/usr/bin/env python3
"""On the chip: record the small trace the reduction is checked on.

    python3 perfbench/checks/record_trace.py [out_dir]

A few jitted matrix products under the benchmark's own span names with
host sleeps between them, so that the trace has busy time, idle gaps
inside ``entry_call`` and idle gaps inside ``tile_input``.  Writes
``tiny.xplane.pb`` and what ``xplane.describe`` and ``xplane.reduce``
make of it; the numbers of ``data/tiny.expected.json`` were read from
that output by hand.
"""
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out"
    import jax
    import jax.numpy as jnp
    from perfbench import xplane
    if jax.default_backend() != "tpu":
        print("record_trace: needs the chip", file=sys.stderr)
        return 2

    @jax.jit
    def tiny_gemm(a):
        return jnp.dot(a, a.T, precision="highest") * (1.0 / 1024)

    x = jnp.ones((1024, 1024), jnp.float32)
    tiny_gemm(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="perfbench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("perfbench:traced"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("perfbench:tile_input"):
                time.sleep(0.004)
            with jax.profiler.TraceAnnotation("perfbench:entry_call"):
                y = x
                for _ in range(3):
                    y = tiny_gemm(y)
                y.block_until_ready()
                time.sleep(0.002)
    jax.profiler.stop_trace()
    os.makedirs(out, exist_ok=True)
    dst = os.path.join(out, "tiny.xplane.pb")
    shutil.copy(xplane.find_xplane(tmp), dst)
    shutil.rmtree(tmp, ignore_errors=True)
    pd = xplane.load(dst)
    with open(os.path.join(out, "tiny.describe.txt"), "w") as f:
        f.write(xplane.describe(pd, limit=12))
    r = xplane.reduce(xplane.read(pd))
    print(f"record_trace: {os.path.getsize(dst)} bytes; reduce: "
          f"{json.dumps(r)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
