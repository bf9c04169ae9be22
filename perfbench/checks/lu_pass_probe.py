#!/usr/bin/env python3
"""On the chip, by hand: what a STRIP PASS of LU's pivoted panel costs,
by operation and with its bytes, in the parent's formulation and in
today's, at the two LU cells' shapes.

    python3 perfbench/checks/lu_pass_probe.py [--shapes 16384x512,32768x1024] [--reps 5] [--seeds 2]

One process, no runtime: ``parsec_tpu.ops.linalg`` alone.  A panel of
NB columns is NB / 32 strips; after each strip the rest of the panel
pays a PASS.  For each shape, at first row 0 and N / 2, five PANEL
programs (``getrf_1d_panel`` built on each):

- ``parent``: the formulation before a pass moved only what the strip
  changed (kept in ``tests/test_lu_panel_pass.py``: a whole-panel gather
  and two whole-panel rewrites a strip), on the strip kernel;
- ``parent_passes``: the same on a strip that does nothing, so that the
  passes are read alone;
- ``new``: ``ops.linalg._lu_panel`` as the platform lowers it (the strip
  kernel and the pass kernel ``lu_pass_vmem``);
- ``new_passes``: the same on a strip that does nothing (its pivot rows
  are spread over the active rows, as real ones are);
- ``xla_passes``: today's panel with the pass left to XLA
  (``ops.linalg._lu_pass``: the moved rows as small scatters, the
  product written back in place), on a strip that does nothing:
  candidate (a) of ISSUE 41, which every platform but the TPU runs.

Of each: the host's clock around a call that ends in
``block_until_ready``, the device's own time from a profiler trace, and
the device time of a panel BY KIND OF OPERATION (opcode, fusion kind or
kernel name, and result shape), with the bytes the results of that kind
hold read once and written once, and beside the pass kernel the bytes
its windows really move and the bytes a pass HAS to move (the rows that
moved and the columns right of the strip under the panel's first row).
A pass is read twice: from the programs whose strips do nothing, and, as
the benchmark's ``panel_pass_device_s`` reads it, as a panel on the real
strips less the strip kernel (the parent's program on strips that do
nothing is another program: XLA schedules its copies otherwise).

Then the same result: for ``--seeds`` seeded block columns a shape, the
new panel's pivot tile equals the parent's on the chip; the entries of
the factored column that differ are counted, with the largest distance
in units of the last place.

Prints one JSON object last and writes it to
``chiprun_out/lu_pass_probe.json``.  Refuses to run without a TPU: a
time here is a chip time (``--rehearse``: tiny shapes, interpreted
kernels, no device time, for the CPU).  Never run by the benchmark's own
runs.
"""
import argparse
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import xplane  # noqa: E402
from perfbench.checks import lu_strip_probe as strip_probe  # noqa: E402

OP = re.compile(r"^%?(?P<name>[^ ]+?)(\.\d+)? = (?P<shape>\(.*?\)|\S+) "
                r"(?P<opcode>[\w\-]+)\(")


def by_kind(ops_s, reps):
    """{kind: [count, ms a panel, MB its results hold read once and
    written once]} of a trace's operations, largest first.  A kind is
    the opcode (a fusion's kind, a custom call's target or kernel name)
    and the result's shape."""
    kinds = {}
    for op, sec in ops_s.items():
        m = OP.match(op)
        if not m:
            key, mb = xplane.short_name(op), 0.0
        else:
            what = m["opcode"]
            kind = re.search(r"kind=(\w+)", op)
            if what == "fusion" and kind:
                what = "fusion " + kind[1]
            elif what == "custom-call":
                what = re.sub(r"\.\d+$", "", m["name"]) \
                    if "tpu_custom_call" in op else xplane.short_name(op)
            shape = re.sub(r"\{[^}]*\}", "", m["shape"])
            key = f"{what} -> {shape}"
            mb = 2 * sum(4 * math.prod(int(d) for d in dims.split(",") if d)
                         for dims in re.findall(r"[fs]32\[([\d,]*)\]", shape)
                         ) / 1e6
        row = kinds.setdefault(key, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += sec * 1e3 / reps
        row[2] += mb
    return sorted(([k] + v for k, v in kinds.items()), key=lambda r: -r[2])[:14]


def traced(fn, args, reps):
    """(milliseconds the device was busy for one ``fn(*args)``, {operation:
    device seconds of all ``reps`` calls}) from one profiler trace."""
    import jax
    trace_dir = tempfile.mkdtemp(prefix="lu_pass_probe_")
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
        return None, {}
    return tr["busy_s"] * 1e3 / reps, tr["ops_s"]


def pass_megabytes(n, nb, r, w, window, blk):
    """(MB the pass kernel's windows move a panel, MB the passes have to
    move a panel): windows run from the strip's lane tile to the right
    edge over the blocks of rows from the one holding the strip's first
    row down, read once and written once; what has to move is the
    columns right of the strip over the rows under the panel's first
    row, and the 2 w rows that moved."""
    moved = needed = 0
    for c0 in range(0, nb, w):
        c1 = min(c0 + w, nb)
        lo = window(c0)
        if lo < c0 or c1 < nb:
            moved += 2 * 4 * (n - (r + c0) // blk * blk) * (nb - lo)
        needed += 2 * 4 * ((n - r - c0) * (nb - c1) + 2 * w * (nb - c1))
    return moved / 1e6, needed / 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="16384x512,32768x1024")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"lu_pass_probe: needs a TPU, found {dev.platform}")
    jax.config.update("jax_default_matmul_precision", "highest")
    from parsec_tpu.ops import linalg, pallas_kernels
    spec = importlib.util.spec_from_file_location(
        "parent_panel", os.path.join(ROOT, "tests", "test_lu_panel_pass.py"))
    parent = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(parent)

    w = linalg.LU_STRIP
    strip = linalg._lu_strip_lowered
    xla_pass = linalg._lu_pass
    if args.rehearse:       # the kernels, interpreted
        def strip(st, d0):
            return pallas_kernels.lu_strip_vmem(st, d0, interpret=True)

    def no_strip(st, d0):
        # the identity, in a form the compiler cannot see through; the
        # pivot rows spread over the active rows
        n = st.shape[1]
        lane = jnp.arange(n, dtype=jnp.int32)
        i = jnp.arange(st.shape[0], dtype=jnp.int32)
        return (st, jnp.where(lane == d0, d0, lane),
                d0 + (i * 7919 + 13) % (n - d0))

    def panel_on(name, lu_panel, strip, lu_pass=None):
        def panel(a, q):
            kept = (linalg._lu_panel, linalg._lu_strip_lowered,
                    linalg._lu_pass_lowered)
            linalg._lu_panel, linalg._lu_strip_lowered = lu_panel, strip
            if lu_pass is not None:
                linalg._lu_pass_lowered = lu_pass
            try:
                return linalg.getrf_1d_panel.__wrapped__(a, q)
            finally:
                (linalg._lu_panel, linalg._lu_strip_lowered,
                 linalg._lu_pass_lowered) = kept
        panel.__name__ = "PANEL_" + name
        return jax.jit(panel)

    def programs():
        interpreted = None
        if args.rehearse:
            def interpreted(x, st, rows, new, d0, *, c0, c1):
                return pallas_kernels.lu_pass_vmem(x, st, rows, new, d0,
                                                   c0=c0, c1=c1,
                                                   interpret=True)
        return {
            "parent": panel_on("parent", parent.parent_lu_panel, strip),
            "parent_passes": panel_on("parent_passes",
                                      parent.parent_lu_panel, no_strip),
            "new": panel_on("new", linalg._lu_panel, strip, interpreted),
            "new_passes": panel_on("new_passes", linalg._lu_panel, no_strip,
                                   interpreted),
            "xla_passes": panel_on("xla_passes", linalg._lu_panel, no_strip,
                                   xla_pass),
        }

    report = {"device": dev.device_kind, "w": w, "reps": args.reps,
              "shapes": []}
    for shape in args.shapes.split(","):
        n, nb = (int(v) for v in shape.split("x"))
        passes = nb // w
        panels = programs()

        def pivot_tile(r):
            q = np.zeros((linalg.PIV_ROWS, n), np.int32)
            q[0, 0], q[2] = r, np.arange(n)
            return jnp.asarray(q)

        rng = np.random.default_rng(2 ** 31 + 41 + n)
        col = jnp.asarray(rng.standard_normal((n, nb)).astype(np.float32))
        entry = {"n": n, "nb": nb, "passes_a_panel": passes, "panel": [],
                 "same": []}
        print(f"device {dev.device_kind}; panel ({n}, {nb}), {passes} strips",
              flush=True)
        for r in (0, n // 2):
            row = {"r": r}
            row["window_mb"], row["needed_mb"] = pass_megabytes(
                n, nb, r, w, pallas_kernels.lu_pass_window,
                pallas_kernels._LU_PASS_ROWS)
            for label, fn in panels.items():
                a = (col, pivot_tile(r))
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*a))
                row[f"{label}_first_call_s"] = time.perf_counter() - t0
                row[f"{label}_host_ms"] = strip_probe.host_ms(fn, a, args.reps)
                if args.rehearse:
                    continue
                row[f"{label}_device_ms"], ops = traced(fn, a, args.reps)
                row[f"{label}_by_kind"] = by_kind(ops, args.reps)
                print(f"{label}, r = {r}, device ms a panel "
                      f"{row[f'{label}_device_ms']:.3f}; by kind [kind, ops, "
                      f"ms, MB]:", flush=True)
                for k in row[f"{label}_by_kind"]:
                    print("   ", json.dumps(k), flush=True)
            if not args.rehearse:
                for label in ("parent", "new", "xla"):
                    row[f"{label}_pass_ms"] = \
                        row[f"{label}_passes_device_ms"] / passes
                # and as the benchmark reads them (panel_pass_device_s): a
                # panel on the real strips, less the strip kernel
                for label in ("parent", "new"):
                    strips = sum(ms for kind, _, ms, _ in row[f"{label}_by_kind"]
                                 if kind.startswith("lu_strip_vmem "))
                    row[f"{label}_less_strips_pass_ms"] = \
                        (row[f"{label}_device_ms"] - strips) / passes
                row["less_strips_fall_pct"] = 100 * (
                    1 - row["new_less_strips_pass_ms"]
                    / row["parent_less_strips_pass_ms"])
                row["passes_fall_pct"] = 100 * (
                    1 - row["new_passes_device_ms"]
                    / row["parent_passes_device_ms"])
                row["xla_passes_fall_pct"] = 100 * (
                    1 - row["xla_passes_device_ms"]
                    / row["parent_passes_device_ms"])
            entry["panel"].append(row)
            print(json.dumps({k: v for k, v in row.items()
                              if not k.endswith("_by_kind")}), flush=True)

        # the same result on the chip
        for s in range(args.seeds):
            seed = 2 ** 31 + 4100 + s
            a = jnp.asarray(np.random.default_rng(seed).standard_normal(
                (n, nb)).astype(np.float32))
            r = s * (n // 2) // max(1, args.seeds - 1) // nb * nb   # 0 .. n/2
            got = panels["new"](a, pivot_tile(r))
            want = panels["parent"](a, pivot_tile(r))
            differs, far = strip_probe.ulps(np.asarray(got[0]),
                                            np.asarray(want[0]))
            row = {"seed": seed, "r": r,
                   "pivots_equal": bool(np.array_equal(got[1][3], want[1][3])),
                   "pivot_tile_equal": bool(np.array_equal(got[1], want[1])),
                   "column_differs": differs, "column_ulps": far,
                   "largest_multiplier": float(np.abs(np.tril(
                       np.asarray(got[0])[r:], -1)).max())}
            entry["same"].append(row)
            print(json.dumps(row), flush=True)
        report["shapes"].append(entry)

    os.makedirs(args.out, exist_ok=True)
    name = "lu_pass_probe_rehearsal.json" if args.rehearse \
        else "lu_pass_probe.json"
    with open(os.path.join(args.out, name), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    ok = all(r["pivots_equal"] for e in report["shapes"] for r in e["same"])
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
