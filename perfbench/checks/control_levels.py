#!/usr/bin/env python3
"""On the chip: the readings the three level limits of a mixed-precision
configuration are set from, one level lowered at a time.

    python3 perfbench/checks/control_levels.py --workload <cell> \
        --seeds 10 --control-seeds 3 [--variants sound,hi_high,...]

One process.  For each seed it runs the cell's own timed path
(``run.Factorizer``: the same entry point, tiling and sizes) as it is
(``sound``) and, on the first ``--control-seeds`` seeds, once more for
each control on the same input, and prints the three level numbers of
``reference/cholesky_mp.py`` beside their limits.  The controls, each
computing ONE level a step lower than the configuration states:

- ``hi_high``: the process's matmul precision ``high`` (what
  ``control.py`` does: the hi band follows it, mid and lo state their
  own);
- ``mid_one_pass``: ``ops.gemm_nt_mid`` and ``ops.trsm_panel_mid`` with
  their products in one bf16 pass;
- ``lo_bf16_acc``: ``ops.gemm_nt_lo`` with its product accumulated and
  handed back in bf16;
- ``lo_4bit``: ``ops.gemm_nt_lo`` with its bf16 operands rounded to 4
  significant bits (half of bf16's 8 again, as 8 is half of mid's 16),
  bf16's exponent kept: float8_e4m3 has the bits but not the range (the
  far tiles' entries lie under its smallest number);
- ``lo_in_hi``: a broken program, not a control: ``ops.gemm_nt`` (the
  hi band's kernel) computing in one bf16 pass.

A sound run has to pass every limit; a control has to miss its level's
and no other.  Never run by the benchmark's own runs.
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

CONTROLS = ("hi_high", "mid_one_pass", "lo_bf16_acc", "lo_4bit", "lo_in_hi")


def kernels_of(variant, ops):
    """{name in ``ops``: replacement} of one variant."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.ops import linalg
    one = jax.lax.Precision.DEFAULT
    f32, bf16 = jnp.float32, jnp.bfloat16

    def nt(c, a, b, **kw):
        return c - jnp.dot(a, b.T, **kw).astype(f32)

    if variant == "mid_one_pass":
        return {"gemm_nt_mid": jax.jit(lambda c, a, b: nt(
                    c, a, b, precision=one, preferred_element_type=f32)),
                "trsm_panel_mid": jax.jit(lambda t, c: linalg.trsm_panel_split(
                    t, c, one, linalg.TRSM_LEAF))}
    if variant == "lo_bf16_acc":
        return {"gemm_nt_lo": jax.jit(lambda c, a, b: nt(
            c, a, b, precision=one, preferred_element_type=bf16))}
    if variant == "lo_4bit":
        def four(x):    # to nearest even on the 4 mantissa bits dropped
            u = jax.lax.bitcast_convert_type(x, jnp.uint16)
            u = (u + ((u >> 4) & 1) + 7) >> 4 << 4
            return jax.lax.bitcast_convert_type(u, bf16)
        return {"gemm_nt_lo": jax.jit(lambda c, a, b: nt(
            c, four(a), four(b), precision=one, preferred_element_type=f32))}
    if variant == "lo_in_hi":
        return {"gemm_nt": jax.jit(lambda c, a, b: nt(
            c, a.astype(bf16), b.astype(bf16), precision=one,
            preferred_element_type=f32))}
    return {}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=10)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--variants", default="sound," + ",".join(CONTROLS))
    ap.add_argument("--base", type=int, default=2 ** 31 + 1000)
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
    stated = cell.config["matmul_precision"]
    jax.config.update("jax_default_matmul_precision", stated)
    device, _ = harness.gate_device(jax, cell, bool(args.rehearse))
    import parsec_tpu
    from parsec_tpu import ops
    ref = cell.reference()
    limits = ref.limits()
    variants = args.variants.split(",")
    readings = {v: [] for v in variants}
    ctx = parsec_tpu.init()
    fz = harness.Factorizer(jax, ctx, cell, None, harness.HostClocks(jax))
    try:
        for i in range(args.seeds):
            seed = args.base + 7919 * i
            M = fz.M = harness.seeded_input(ref, cell, seed)
            exp = ref.expected(M, seed)
            for variant in variants:
                if variant != "sound" and i >= args.control_seeds:
                    continue
                swapped = kernels_of(variant, ops)
                sound = {k: getattr(ops, k) for k in swapped}
                jax.config.update("jax_default_matmul_precision",
                                  "high" if variant == "hi_high" else stated)
                for k, fn in swapped.items():
                    setattr(ops, k, fn)
                try:
                    operands = fz.tile()
                    wall, _, why = fz.factor(operands)
                    # the first call of a variant builds its programs
                    operands = fz.tile()
                    wall, d, why = fz.factor(operands)
                finally:
                    for k, fn in sound.items():
                        setattr(ops, k, fn)
                    jax.config.update("jax_default_matmul_precision", stated)
                t = time.perf_counter()
                numbers = ref.level_numbers(fz.pull(operands), exp)
                readings[variant].append(numbers)
                print(f"levels {cell.name} {variant} seed {seed}: "
                      + ", ".join(f"{lv} {numbers[lv]:.6e} (limit "
                                  f"{limits[lv]:g})" for lv in ref.LEVELS)
                      + f"; factor {wall:.4f} s, conversions "
                        f"{sum(x.get('conversions', 0) for x in d)}, check "
                        f"{time.perf_counter() - t:.1f} s"
                      + (f" FAILED: {why}" if why else ""), flush=True)
                del operands
            del M, exp
    finally:
        ctx.fini()
    summary = {"cell": cell.name, "device": device, "limits": limits,
               "readings": readings,
               "largest": {v: {lv: max(r[lv] for r in rs)
                               for lv in ref.LEVELS}
                           for v, rs in readings.items() if rs},
               "smallest": {v: {lv: min(r[lv] for r in rs)
                                for lv in ref.LEVELS}
                            for v, rs in readings.items() if rs}}
    print(json.dumps(summary), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"levels_{cell.name}.json"), "w") as f:
        json.dump(summary, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
