#!/usr/bin/env python
"""Turbo per-task dispatch breakdown (round-4 VERDICT item 4).

Splits the measured per-task cost into its layers:

  loop_us      C NativeDAG.run_loop select/release with a NO-OP
               trampoline (the reference's scheduling.c:586-625 does
               this part in ~1 us of generated C)
  entry_us     + Python trampoline & entry unpack, still no XLA call
  submit_us    full async submission: one pre-bound AOT executable
               call per task, clock stops BEFORE the device sync
               (CPU-side framework cost — the number turbo can
               actually control)
  wall_us      + device execution to completion (block_until_ready)
  classic_us   the dynamic-hash + scheduler + device-module per-task
               path on the same DAG shape, CPU-side dispatch

Usage: python tools/turbo_profile.py [N [NB]]   (default 4096 512)
Prints one JSON line; run on the real chip or CPU.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def main() -> int:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
    nb = int(sys.argv[2]) if len(sys.argv) > 2 else 512

    import jax

    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.dsl.ptg.turbo import TurboRunner
    from parsec_tpu.ops import dpotrf_taskpool, make_spd
    from parsec_tpu.utils.params import params

    params.set_cmdline("ptg_dep_management", "static")
    dev = jax.devices()[0]
    M = make_spd(n, dtype=np.float32)

    def fresh_runner():
        A = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)
        return TurboRunner(dpotrf_taskpool(A))

    r = fresh_runner()
    ntasks = r.dag.n_tasks
    pools = r.build_pools(device=dev)
    jax.block_until_ready(pools)
    pools = r.execute_per_task(pools, device=dev)   # warm compiles
    jax.block_until_ready(pools)

    prio = np.ascontiguousarray(r.dag.priority, np.int32)
    indptr, succ, indeg = r._aug
    if r._make_aug_engine(indptr, succ, indeg) is None:
        # pure-Python install: execute_per_task falls back to the Python
        # loop, but the C-loop breakdown below has nothing to measure
        print("native extension not built: no C run_loop to profile "
              "(build with `python -m parsec_tpu.native.build`)")
        return 1

    def best_of(f, reps=3):
        b = None
        for _ in range(reps):
            t0 = time.perf_counter()
            f()
            dt = time.perf_counter() - t0
            b = dt if b is None or dt < b else b
        return b

    # 1) bare C loop: select/release over the augmented CSR, no work
    t_loop = best_of(lambda: r._make_aug_engine(indptr, succ, indeg)
                     .run_loop(lambda tid: None, prio))

    # 2) + trampoline & entry unpack (the Python per-task fixed cost)
    entries = r._entries

    def entry_only(tid):
        fn, a = entries[tid]
        _ = a["locs"], a["idx_in"], a["idx_out"], a["idx_wbx"]

    t_entry = best_of(lambda: r._make_aug_engine(indptr, succ, indeg)
                      .run_loop(entry_only, prio))

    # 3) full submission (async) and 4) wall to completion
    t_submit = []
    t_wall = []
    for _ in range(3):
        rr = fresh_runner()
        pp = rr.build_pools(device=dev)
        jax.block_until_ready(pp)
        t0 = time.perf_counter()
        pp = rr.execute_per_task(pp, device=dev)
        t_submit.append(rr.stats["dispatch_secs"])
        jax.block_until_ready(pp)
        t_wall.append(time.perf_counter() - t0)
    aot = not hasattr(entries[0][0], "lower")   # compiled, not a jit fn

    # 5) the classic per-task runtime on the same shape
    import parsec_tpu
    params.unset_cmdline("ptg_dep_management")
    ctx = parsec_tpu.init(nb_cores=1)
    try:
        tdev = [d for d in ctx.devices if d.device_type == "tpu"]
        best_classic = None
        for _ in range(2):
            A = TwoDimBlockCyclic(n, n, nb, nb,
                                  dtype=np.float32).from_numpy(M)
            if tdev:
                for c in A.tiles():
                    tdev[0].data_advise(A.data_of(*c), "prefetch")
                jax.block_until_ready([
                    A.data_of(*c).get_copy(tdev[0].device_index).payload
                    for c in A.tiles()])
            t0 = time.perf_counter()
            ctx.add_taskpool(dpotrf_taskpool(A))
            ctx.wait()
            dt = time.perf_counter() - t0
            best_classic = dt if best_classic is None \
                else min(best_classic, dt)
    finally:
        ctx.fini()

    us = 1e6 / ntasks
    print(json.dumps({
        "metric": f"turbo_dispatch_profile(N={n},NB={nb})",
        "tasks": ntasks,
        "aot_prebound": aot,
        "native_loop": r.stats.get("native_loop"),
        "loop_us": round(t_loop * us, 2),
        "entry_us": round(t_entry * us, 2),
        "submit_us": round(min(t_submit) * us, 2),
        "wall_us": round(min(t_wall) * us, 2),
        "classic_us": round(best_classic * us, 2),
        "submit_speedup_vs_classic": round(best_classic /
                                           min(t_submit), 2),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
