#!/usr/bin/env python3
"""On the chip, by hand: what one strip of LU's pivoted panel costs in
each of its two lowerings, and what a panel is made of.

    python3 perfbench/checks/lu_strip_probe.py [--n 16384] [--nb 512] [--reps 20] [--seeds 3]

One process, no runtime: the kernels of ``parsec_tpu.ops.linalg`` alone.

1. A strip: ``jax.jit(_lu_strip)`` (the XLA loop) and
   ``pallas_kernels.lu_strip_vmem`` (the strip held in VMEM) on a seeded
   (LU_STRIP, n) strip at ``d0`` = 0, n/2 and n - LU_STRIP: the host's
   clock around one call that ends in ``block_until_ready`` (median of
   ``--reps``; a call of nothing is printed beside it, because it is a
   large part of a short call), and the device's own time of the call
   from a profiler trace.
2. A panel: ``getrf_1d_panel`` at (n, nb) built on each lowering, and
   built on a strip that does nothing (the ``nb / LU_STRIP`` strip
   passes alone: the whole-panel gather, the small solve, the product),
   at first row 0 and n/2: host clock and device time, and the device
   time of a panel by operation, so that the split of a panel into
   column steps and strip passes is read, not estimated.  Prints how the
   trace names the Mosaic call.
3. The same result: for ``--seeds`` seeded block columns the kernel's
   pivot rows equal the XLA loop's on the chip; the entries of the
   factored column that differ are counted, with the largest distance
   in units of the last place.

Prints one JSON object last and writes it to
``chiprun_out/lu_strip_probe.json``.  Refuses to run without a TPU: a
time here is a chip time.  Never run by the benchmark's own runs.
"""
import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import xplane  # noqa: E402


def host_ms(fn, args, reps):
    """Median milliseconds of ``fn(*args)`` to ``block_until_ready``."""
    import jax
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(out)


def device_ms(fn, args, reps):
    """(milliseconds the device was busy for one ``fn(*args)``, its
    operations by device time) from a profiler trace of ``reps`` calls."""
    import jax
    jax.block_until_ready(fn(*args))
    trace_dir = tempfile.mkdtemp(prefix="lu_strip_probe_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            for _ in range(reps):
                jax.block_until_ready(fn(*args))
        finally:
            jax.profiler.stop_trace()
        tr = xplane.reduce(xplane.read(xplane.load(
            xplane.find_xplane(trace_dir))))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if tr is None:
        return None, []
    ops = [[name, sec * 1e3 / reps] for name, sec in xplane.top(tr["ops_s"], 12)]
    return tr["busy_s"] * 1e3 / reps, ops


def ulps(a, b):
    """Entries that differ and the largest distance in units of the last
    place between two float32 arrays of one sign pattern."""
    import numpy as np
    ia = a.view(np.int32).astype(np.int64)
    ib = b.view(np.int32).astype(np.int64)
    ia = np.where(ia < 0, -(ia & 0x7fffffff), ia)
    ib = np.where(ib < 0, -(ib & 0x7fffffff), ib)
    dist = np.abs(ia - ib)
    return int((dist > 0).sum()), int(dist.max())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=16384)
    ap.add_argument("--nb", type=int, default=512)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"lu_strip_probe: needs a TPU, found {dev.platform}")
    jax.config.update("jax_default_matmul_precision", "highest")
    from parsec_tpu.ops import linalg, pallas_kernels

    n, nb, w = args.n, args.nb, linalg.LU_STRIP
    report = {"device": dev.device_kind, "n": n, "nb": nb, "w": w,
              "reps": args.reps, "strip": [], "panel": [], "same": []}
    print(f"device {dev.device_kind}; strip ({w}, {n}), panel ({n}, {nb})",
          flush=True)
    nothing = jax.jit(lambda d0: d0 + 1)
    report["call_of_nothing_ms"] = host_ms(nothing, (jnp.int32(0),), args.reps)
    print(f"a jitted call of nothing: {report['call_of_nothing_ms']:.4f} ms",
          flush=True)

    # 1. one strip
    xla_strip = jax.jit(linalg._lu_strip)
    vmem_strip = jax.jit(pallas_kernels.lu_strip_vmem)
    rng = np.random.default_rng(2 ** 31 + 36)
    st = jnp.asarray(rng.standard_normal((w, n)).astype(np.float32))
    for d0 in (0, n // 2, n - w):
        row = {"d0": d0}
        for label, fn in (("xla", xla_strip), ("vmem", vmem_strip)):
            a = (st, jnp.int32(d0))
            row[f"{label}_host_ms"] = host_ms(fn, a, args.reps)
            row[f"{label}_device_ms"], ops = device_ms(fn, a, args.reps)
            if d0 == 0:
                row[f"{label}_ops_ms"] = ops
        got, want = vmem_strip(st, jnp.int32(d0)), xla_strip(st, jnp.int32(d0))
        row["pivots_equal"] = bool(np.array_equal(got[2], want[2]))
        row["gather_equal"] = bool(np.array_equal(got[1], want[1]))
        row["strip_differs"], row["strip_ulps"] = ulps(
            np.asarray(got[0]), np.asarray(want[0]))
        row["speedup_device"] = row["xla_device_ms"] / row["vmem_device_ms"]
        report["strip"].append(row)
        print(json.dumps({k: v for k, v in row.items()
                          if not k.endswith("_ops_ms")}), flush=True)

    # 2. one panel, on each lowering and on no strip at all
    def panel_on(strip):
        def panel(a, q):
            linalg._lu_strip_lowered, kept = strip, linalg._lu_strip_lowered
            try:
                return linalg.getrf_1d_panel.__wrapped__(a, q)
            finally:
                linalg._lu_strip_lowered = kept
        panel.__name__ = f"PANEL_{strip.__name__.strip('_')}"
        return jax.jit(panel)

    def no_strip(st, d0):
        # the identity, in a form the compiler cannot see through
        lane = jnp.arange(st.shape[1], dtype=jnp.int32)
        return (st, jnp.where(lane == d0, d0, lane),
                jnp.zeros((st.shape[0],), jnp.int32) + d0)

    panels = {"xla": panel_on(linalg._lu_strip),
              "vmem": panel_on(pallas_kernels.lu_strip_vmem),
              "passes": panel_on(no_strip)}

    def pivot_tile(r):
        q = np.zeros((linalg.PIV_ROWS, n), np.int32)
        q[0, 0], q[2] = r, np.arange(n)
        return jnp.asarray(q)

    col = jnp.asarray(rng.standard_normal((n, nb)).astype(np.float32))
    for r in (0, n // 2):
        row = {"r": r}
        for label, fn in panels.items():
            a = (col, pivot_tile(r))
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            row[f"{label}_first_call_s"] = time.perf_counter() - t0
            row[f"{label}_host_ms"] = host_ms(fn, a, args.reps)
            row[f"{label}_device_ms"], ops = device_ms(fn, a, 5)
            if r == 0:
                row[f"{label}_ops_ms"] = ops
                print(f"panel on {label}, r = 0, device ms a panel by "
                      f"operation: {ops}", flush=True)
        row["steps_ms_xla"] = row["xla_device_ms"] - row["passes_device_ms"]
        row["steps_ms_vmem"] = row["vmem_device_ms"] - row["passes_device_ms"]
        report["panel"].append(row)
        print(json.dumps({k: v for k, v in row.items()
                          if not k.endswith("_ops_ms")}), flush=True)

    # 3. the same result on the chip
    for s in range(args.seeds):
        seed = 2 ** 31 + 3600 + s
        a = jnp.asarray(np.random.default_rng(seed).standard_normal(
            (n, nb)).astype(np.float32))
        r = s * (n // 2) // max(1, args.seeds - 1) // nb * nb   # 0 .. n/2
        got = panels["vmem"](a, pivot_tile(r))
        want = panels["xla"](a, pivot_tile(r))
        differs, far = ulps(np.asarray(got[0]), np.asarray(want[0]))
        row = {"seed": seed, "r": r,
               "pivots_equal": bool(np.array_equal(got[1][3], want[1][3])),
               "pivot_tile_equal": bool(np.array_equal(got[1], want[1])),
               "column_differs": differs, "column_ulps": far}
        report["same"].append(row)
        print(json.dumps(row), flush=True)

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "lu_strip_probe.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: report[k] for k in ("strip", "panel", "same")}))
    ok = all(r["pivots_equal"] for r in report["strip"] + report["same"])
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
