#!/usr/bin/env python3
"""On the chip: the readings a ``check.limit`` is set from.

    python3 perfbench/checks/control.py --workload <cell> \
        --precisions highest,high,default --seeds 12 [--base 100]

One process.  For each matmul precision in turn it runs the cell's own
timed path (``run.Factorizer``: the same entry point, tiling and sizes)
once per seed and prints the number the cell's check compares, beside
the configuration's limit.  ``highest`` (what the configurations state)
gives the sound runs' largest; ``high`` (three bf16 passes, the nearest
precision below) is the control and has to miss the limit; ``default``
(one bf16 pass) is read as well.  Never run by the benchmark's own runs.
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
    ap.add_argument("--precisions", default="highest,high,default")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--base", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--out", default="chiprun_out")
    args = ap.parse_args()

    from perfbench import spec
    import run as harness
    cell = spec.Cell(spec.load_benchmark(), args.workload)
    import jax
    device, _ = harness.gate_device(jax, cell, rehearse=False)
    import parsec_tpu
    ref = cell.reference()
    limit = float(cell.config["check"]["limit"])
    readings = {}
    ctx = parsec_tpu.init()
    fz = harness.Factorizer(jax, ctx, cell, None, harness.HostClocks(jax))
    try:
        for precision in args.precisions.split(","):
            jax.config.update("jax_default_matmul_precision", precision)
            readings[precision] = []
            for i in range(args.seeds):
                seed = args.base + 7919 * i
                M = fz.M = harness.seeded_input(ref, cell, seed)
                operands = fz.tile()
                wall, _, why = fz.factor(operands)
                t = time.perf_counter()
                res = ref.residual(fz.pull(operands), ref.expected(M, seed))
                readings[precision].append(res)
                print(f"control {cell.name} {precision} seed {seed}: "
                      f"residual {res:.6e} (limit {limit:g}) factor "
                      f"{wall:.3f} s check {time.perf_counter() - t:.1f} s"
                      f"{' FAILED: ' + why if why else ''}", flush=True)
                del operands, M
    finally:
        ctx.fini()
    summary = {"cell": cell.name, "device": device, "limit": limit,
               "readings": readings,
               "largest": {p: max(v) for p, v in readings.items()},
               "smallest": {p: min(v) for p, v in readings.items()}}
    print(json.dumps(summary), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"control_{cell.name}.json"), "w") as f:
        json.dump(summary, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
