#!/usr/bin/env python3
"""On the chip: the two readings the stencil configuration's limit is set
from.

    python3 perfbench/checks/control_stencil.py --workload <cell> \
        --seeds 6 --control-seeds 3

One process.  For each seed it runs the cell's own timed path
(``run.Factorizer``: the same entry point, tiling and sizes) as it is
(``sound``) and, on the first ``--control-seeds`` seeds, once more on
the same input with ``ops.stencil_tile`` handing back its tile rounded
to bf16 (``lax.reduce_precision`` to 8 exponent and 7 mantissa bits):
the same steps in the nearest precision below the one the
configuration states, once a step (``checks/control.py`` sweeps the
matmul precision, which a stencil never consults).  It prints the
number the cell's check compares, and its two parts, beside the limit.
A sound run has to pass and a control to miss.  Never run by the
benchmark's own runs.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--base", type=int, default=2 ** 31 + 3000)
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--rehearse", default="", metavar="N,NB",
                    help="CPU dry run at a tiny size: counts, no reading")
    args = ap.parse_args()

    from perfbench import spec
    import run as harness
    cell = spec.Cell(spec.load_benchmark(), args.workload)
    if args.rehearse:
        n, nb = (int(x) for x in args.rehearse.split(","))
        cell.resize(N=n, NB=nb)
    import jax
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    device, _ = harness.gate_device(jax, cell, bool(args.rehearse))
    import parsec_tpu
    from parsec_tpu import ops
    ref = cell.reference()
    limit = float(cell.config["check"]["limit"])
    sound = ops.stencil_tile
    # ``reduce_precision`` and not a cast to bfloat16 and back: XLA on
    # the TPU takes such a pair of conversions out (excess precision
    # is allowed), and the "control" then reads as the sound run does
    # (my chip run, PR 46)
    control = jax.jit(
        lambda x, left=None, right=None, weights=ref.WEIGHTS:
        jax.lax.reduce_precision(sound(x, left, right, weights),
                                 exponent_bits=8, mantissa_bits=7),
        static_argnames=("weights",))
    readings = {"sound": [], "bf16_a_step": []}
    ctx = parsec_tpu.init()
    fz = harness.Factorizer(jax, ctx, cell, None, harness.HostClocks(jax))
    try:
        for i in range(args.seeds):
            seed = args.base + 7919 * i
            M = fz.M = harness.seeded_input(ref, cell, seed)
            exp = ref.expected(M, seed)
            for variant, kernel in (("sound", sound),
                                    ("bf16_a_step", control)):
                if variant != "sound" and i >= args.control_seeds:
                    continue
                ops.stencil_tile = kernel
                try:
                    operands = fz.tile()
                    wall, _, why = fz.factor(operands)
                finally:
                    ops.stencil_tile = sound
                t = time.perf_counter()
                out = fz.pull(operands)
                numbers = {"probes": ref.probe_number(out, exp),
                           "rows": ref.rows_number(out, exp)}
                numbers["compared"] = max(numbers.values())
                readings[variant].append(numbers)
                print(f"control {cell.name} {variant} seed {seed}: compared "
                      f"{numbers['compared']:.6e} (probes "
                      f"{numbers['probes']:.6e}, rows "
                      f"{numbers['rows']:.6e}; limit {limit:g}); call "
                      f"{wall:.4f} s, check {time.perf_counter() - t:.1f} s"
                      + (f" FAILED: {why}" if why else ""), flush=True)
                del operands, out
            del M, exp
    finally:
        ctx.fini()
    summary = {"cell": cell.name, "device": device, "limit": limit,
               "readings": readings,
               "largest_sound": max(r["compared"]
                                    for r in readings["sound"]),
               "smallest_control": min(
                   (min(r["probes"], r["rows"])
                    for r in readings["bf16_a_step"]), default=None)}
    print(json.dumps(summary), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"control_{cell.name}.json"), "w") as f:
        json.dump(summary, f)
    ok = summary["largest_sound"] <= limit and all(
        not min(r["probes"], r["rows"]) <= limit
        for r in readings["bf16_a_step"])
    print("as it should be" if ok else "NOT as it should be", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
