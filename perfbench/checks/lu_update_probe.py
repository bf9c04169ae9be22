#!/usr/bin/env python3
"""On the chip, by hand: what an UPDATE of LU (one block column right of
a panel) costs, by operation, in the parent's formulation and in the
candidates for a walk over the active rows only, at the two LU cells'
shapes.

    python3 perfbench/checks/lu_update_probe.py [--shapes 16384x512,32768x1024] [--reps 3] [--seeds 4]

One process, no runtime: ``parsec_tpu.ops.linalg`` alone.  An UPDATE
program is timed as the runtime dispatches it: alone (``jit_UPDATE``)
and stacked, sixteen columns of one panel in one program, one subgraph a
task (``jit_UPDATE_x16``), the column NOT donated.  For each shape, at
panel index k = 0, NT / 2 and NT - 2 (first row r = k NB: one program
serves them all, r is an operand), on a panel ``getrf_1d_panel`` really
factored:

- ``parent``: the formulation before UPDATE touched only the active
  rows (kept in ``tests/test_lu_update.py``: a gather of all N rows,
  the product over all N rows with the upper ones masked to zero);
- ``kernel``: candidate (a) of ISSUE 43 as ``ops.pallas_kernels.
  lu_update_vmem`` has it: ONE Mosaic kernel that walks the column,
  stores the moved rows and subtracts the product under the block row;
  it writes a NEW column, and the blocks above the panel's first row
  pass through.  (To the issue's letter, the
  column aliased in and out and the upper blocks never brought in, it
  was read once, in PR 43: XLA copies the whole column before a call
  that writes an operand the runtime did not donate, 0.089 / 0.418 ms
  a task, and the walk came out 17 / 14% slower than the one that
  passes the upper blocks through: PERF.md section 5.)
- ``xla_active``: candidate (b), the product left to XLA over the
  active rows only: a loop with a DYNAMIC trip count over (NB, NB)
  blocks of rows from the block row down, the moved rows as a scatter;
- ``xla_walk``: ``ops.linalg._lu_update``, what every platform but the
  TPU runs (the moved rows as a scatter, the product masked as the
  parent's): what leaving the gather alone gives.

Of each: the host's clock around a call that ends in
``block_until_ready``, the device's own time from a profiler trace, the
device time BY KIND OF OPERATION (opcode, fusion kind or kernel name,
and result shape), and the time of a whole factorization's updates: step
k weighs its NT - 1 - k tasks, the steps between the three read by
straight lines.  Then ISSUE 43's rule: a candidate goes in only if it is
faster than the other at BOTH shapes and takes at least 30% off the
parent's weighted time at both.

Then the same result: for ``--seeds`` seeded columns a shape, each
candidate against the parent on the chip: the entries above row r + NB
that differ (the interchange and the solve: 0 expected) and, under it,
the largest distance in units of the last place of the column's largest
entry (``tests/conftest.py::assert_ulp_close``'s measure; 8 allowed).

Prints one JSON object last and writes it to
``chiprun_out/lu_update_probe.json``.  Refuses to run without a TPU: a
time here is a chip time (``--rehearse``: tiny shapes, interpreted
kernels, no device time, for the CPU).  Never run by the benchmark's own
runs.
"""
import argparse
import functools
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.checks.lu_pass_probe import by_kind, traced  # noqa: E402
from perfbench.checks.lu_strip_probe import host_ms  # noqa: E402

STACK = 16
CANDIDATES = ("kernel", "xla_active")


def weighted_ms(nt, ks, ms):
    """Milliseconds of one factorization's updates: step k has NT - 1 -
    k of them, each ``ms`` at the steps ``ks`` and on straight lines
    between."""
    import numpy as np
    k = np.arange(nt - 1)
    return float(((nt - 1 - k) * np.interp(k, ks, ms)).sum())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="16384x512,32768x1024")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--programs",
                    default="parent,kernel,xla_active,xla_walk")
    ap.add_argument("--block-rows", type=int, default=0,
                    help="the kernels' block of rows, where not "
                    "pallas_kernels._LU_UPDATE_ROWS")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"lu_update_probe: needs a TPU, found {dev.platform}")
    jax.config.update("jax_default_matmul_precision", "highest")
    from parsec_tpu.ops import linalg, pallas_kernels
    if args.block_rows:
        pallas_kernels._LU_UPDATE_ROWS = args.block_rows
    spec = importlib.util.spec_from_file_location(
        "parent_update", os.path.join(ROOT, "tests", "test_lu_update.py"))
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    parent = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(parent)

    def xla_active(l, c, rows, new, r):
        n, nb = l.shape
        u = new[nb:]
        c = c.at[rows[:nb]].set(new[:nb])
        c = jax.lax.dynamic_update_slice(c, u, (r, 0))

        def block(i, c):
            at = (r + nb + i * nb, 0)
            return jax.lax.dynamic_update_slice(c, linalg.gemm_nn_sub(
                jax.lax.dynamic_slice(c, at, (nb, c.shape[1])),
                jax.lax.dynamic_slice(l, at, (nb, nb)), u), at)

        return jax.lax.fori_loop(0, (n - r - nb) // nb, block, c)

    def update_on(name, walk):
        # ``getrf_1d_update`` itself on another walk, behind ONE jitted
        # callee as the runtime's stacked program finds it
        def update(l, p, c):
            kept = linalg._lu_update_lowered
            linalg._lu_update_lowered = walk
            try:
                return linalg.getrf_1d_update.__wrapped__(l, p, c)
            finally:
                linalg._lu_update_lowered = kept
        update.__name__ = "UPDATE_" + name
        return jax.jit(update)

    updates = {
        "parent": parent.parent_getrf_1d_update,
        "kernel": update_on("kernel", functools.partial(
            pallas_kernels.lu_update_vmem, interpret=args.rehearse)),
        "xla_active": update_on("xla_active", xla_active),
        "xla_walk": update_on("xla_walk", linalg._lu_update),
    }
    updates = {k: v for k, v in updates.items()
               if k in args.programs.split(",")}

    def stacked(name, update):
        # every task brings its own panel and pivot tile, as the
        # runtime's stacked program takes them: nothing of the solve is
        # shared between the subgraphs
        def program(*flat):
            ls, ps, cs = (flat[j * STACK:(j + 1) * STACK] for j in range(3))
            return tuple(update(*a) for a in zip(ls, ps, cs))
        program.__name__ = f"UPDATE_{name}_x{STACK}"
        return jax.jit(program)

    report = {"device": dev.device_kind, "reps": args.reps, "stack": STACK,
              "block_rows": pallas_kernels._LU_UPDATE_ROWS, "shapes": []}
    for shape in args.shapes.split(","):
        n, nb = (int(v) for v in shape.split("x"))
        nt = n // nb
        ks = [0, nt // 2, nt - 2]
        rng = np.random.default_rng(2 ** 31 + 43 + n)
        cols = [jnp.asarray(rng.standard_normal((n, nb)).astype(np.float32))
                for _ in range(STACK)]
        print(f"device {dev.device_kind}; column ({n}, {nb}), NT = {nt}",
              flush=True)

        def panel(k, seed):
            """(the factored panel, its pivot tile) at first row k NB."""
            a = np.random.default_rng(seed).standard_normal(
                (n, nb)).astype(np.float32)
            q = np.zeros((linalg.PIV_ROWS, n), np.int32)
            q[0, 0], q[2] = k * nb, np.arange(n)
            return linalg.getrf_1d_panel(jnp.asarray(a), jnp.asarray(q))

        panels = {k: panel(k, 2 ** 31 + 4300 + k) for k in ks}
        entry = {"n": n, "nb": nb, "nt": nt, "ks": ks, "programs": {},
                 "same": []}
        for name, update in updates.items():
            row = entry["programs"][name] = {}
            for form, fn, count in (("lone", update, 1),
                                    ("stacked", stacked(name, update), STACK)):
                for k in ks:
                    l, p = panels[k]
                    a = (l,) * count + (p,) * count + tuple(cols[:count])
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*a))
                    first = time.perf_counter() - t0
                    if k == ks[0]:
                        row[f"{form}_first_call_s"] = first
                    at = f"{form}_k{k}"
                    row[f"{at}_host_ms"] = host_ms(fn, a, args.reps) / count
                    if args.rehearse:
                        continue
                    ms, ops = traced(fn, a, args.reps)
                    row[f"{at}_device_ms"] = ms / count
                    row[f"{at}_by_kind"] = [
                        [kind, c, t / count, mb]
                        for kind, c, t, mb in by_kind(ops, args.reps)]
                    print(f"{name} {form}, k = {k}: device ms a task "
                          f"{ms / count:.4f}; by kind [kind, ops, ms a task, "
                          f"MB]:", flush=True)
                    for line in row[f"{at}_by_kind"][:8]:
                        print("   ", json.dumps(line), flush=True)
                if not args.rehearse:
                    row[f"{form}_weighted_ms"] = weighted_ms(
                        nt, ks, [row[f"{form}_k{k}_device_ms"] for k in ks])
            print(json.dumps({name: {k: v for k, v in row.items()
                                     if not k.endswith("_by_kind")}}),
                  flush=True)

        # the same result on the chip
        for s in range(args.seeds):
            seed = 2 ** 31 + 4350 + s
            k = s * (nt - 2) // max(1, args.seeds - 1)
            l, p = panel(k, seed)
            c = jnp.asarray(np.random.default_rng(seed + 50).standard_normal(
                (n, nb)).astype(np.float32))
            under = k * nb + nb
            want = np.asarray(parent.parent_getrf_1d_update(l, p, c))
            scale = float(np.finfo(np.float32).eps * np.abs(want).max())
            row = {"seed": seed, "k": k, "largest_entry": float(
                np.abs(want).max())}
            for name, update in updates.items():
                if name == "parent":
                    continue
                got = np.asarray(update(l, p, c))
                row[name] = {
                    "above_differ": int((got[:under].view(np.int32)
                                         != want[:under].view(np.int32)).sum()),
                    "under_ulps_of_largest": float(np.abs(
                        got[under:].astype(np.float64)
                        - want[under:]).max() / scale),
                    "under_differ": int((got[under:] != want[under:]).sum())}
            entry["same"].append(row)
            print(json.dumps(row), flush=True)
        report["shapes"].append(entry)

    # ISSUE 43's rule
    if not args.rehearse and "parent" in updates:
        fall = {name: [100 * (1 - e["programs"][name]["stacked_weighted_ms"]
                              / e["programs"]["parent"]["stacked_weighted_ms"])
                       for e in report["shapes"]]
                for name in updates if name != "parent"}
        report["stacked_weighted_fall_pct"] = fall
        best = [max((n for n in fall if n in CANDIDATES),
                    key=lambda n: fall[n][i], default=None)
                for i in range(len(report["shapes"]))]
        report["rule"] = {
            "fastest_by_shape": best,
            "goes_in": best[0] if len(set(best)) == 1 and best[0]
            and min(fall[best[0]]) >= 30 else None}
        print(json.dumps({"fall_pct": fall, "rule": report["rule"]}),
              flush=True)

    os.makedirs(args.out, exist_ok=True)
    name = "lu_update_probe_rehearsal.json" if args.rehearse \
        else f"lu_update_probe{args.block_rows or ''}.json"
    with open(os.path.join(args.out, name), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: v for k, v in report.items() if k != "shapes"}))
    same = [r[name] for e in report["shapes"] for r in e["same"]
            for name in updates if name != "parent"]
    ok = all(s["above_differ"] == 0 and s["under_ulps_of_largest"] <= 8
             for s in same)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
