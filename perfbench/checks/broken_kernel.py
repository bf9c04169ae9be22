#!/usr/bin/env python3
"""On the chip: a broken tile kernel under the cell's own timed path has
to come out not correct.

    python3 perfbench/checks/broken_kernel.py --workload <cell> \
        --kernel getrf_1d_laswp --returns 0 [--seed N]
    python3 perfbench/checks/broken_kernel.py --workload <cell> \
        --kernel gemm --drops 4

One process.  It runs the cell's timed path (``run.Factorizer``: the same
entry point, tiling and sizes) once as it is and once with
``parsec_tpu.ops.<kernel>`` replaced by a function that hands back its
argument number ``--returns`` unchanged, or with ``--drops N`` by the
kernel itself called without its arguments from number N on, whose
defaults then apply (``gemm(c, a, b, alpha, beta)`` without its
``beta``), and prints the number the cell's check compares beside the
configuration's limit both times: the first has to pass and the second
to miss.  Exit code 0 when both do.  Never run by the benchmark's own
runs.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--kernel", required=True)
    ap.add_argument("--returns", type=int, default=0)
    ap.add_argument("--drops", type=int, default=None)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 4242)
    args = ap.parse_args()

    from perfbench import spec
    import run as harness
    cell = spec.Cell(spec.load_benchmark(), args.workload)
    import jax
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    harness.gate_device(jax, cell, rehearse=False)
    import parsec_tpu
    from parsec_tpu import ops
    ref = cell.reference()
    limit = float(cell.config["check"]["limit"])
    M = harness.seeded_input(ref, cell, args.seed)
    exp = ref.expected(M, args.seed)
    sound = getattr(ops, args.kernel)
    broken = (lambda *a: a[args.returns]) if args.drops is None \
        else (lambda *a: sound(*a[:args.drops]))
    readings = {}
    ctx = parsec_tpu.init()
    fz = harness.Factorizer(jax, ctx, cell, M, harness.HostClocks(jax))
    try:
        for label, kernel in (("sound", sound), ("broken", broken)):
            setattr(ops, args.kernel, kernel)
            operands = fz.tile()
            _, _, why = fz.factor(operands)
            readings[label] = ref.residual(fz.pull(operands), exp)
            print(f"{cell.name} {args.kernel} {label}: residual "
                  f"{readings[label]:.6e} (limit {limit:g})"
                  f"{' FAILED: ' + why if why else ''}", flush=True)
    finally:
        setattr(ops, args.kernel, sound)
        ctx.fini()
    ok = readings["sound"] <= limit and not readings["broken"] <= limit
    print("as it should be" if ok else "NOT as it should be", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
