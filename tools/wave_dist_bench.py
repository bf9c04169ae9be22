#!/usr/bin/env python
"""Distributed wave dpotrf benchmark across real OS processes.

Spawns NP rank processes over the TCP fabric (virtual mesh: each rank
pinned to JAX's host platform), runs dist-wave dpotrf at N/NB, times the
execute() region (pools staged, ranks sync'd before the clock starts),
numerics-gates the assembled factor, and prints one JSON line.

Usage: python tools/wave_dist_bench.py [N [NB [NP]]]   (default 16384 512 2)
Env: WAVE_DIST_DTYPE (float32), WAVE_DIST_REPS (1). The device plane is
ON by default (exchanges go device-to-device; the runner attaches a
DeviceDataPlane per rank on TCP transports); WAVE_DIST_PLANE=0 opts
back into host-byte exchanges.
"""
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def rank_main() -> int:
    import numpy as np

    import parsec_tpu  # noqa: F401  (package path side effects)
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.comm.tcp import TCPCommEngine
    from parsec_tpu.dsl import ptg
    from parsec_tpu.ops import dpotrf_taskpool, make_spd

    rank = int(sys.argv[2])
    nb_ranks = int(sys.argv[3])
    ports = [int(p) for p in sys.argv[4].split(",")]
    n, nb = int(sys.argv[5]), int(sys.argv[6])
    dtype = np.dtype(os.environ.get("WAVE_DIST_DTYPE", "float32"))
    reps = int(os.environ.get("WAVE_DIST_REPS", "1"))

    M = make_spd(n, dtype=dtype)
    eng = TCPCommEngine(rank, [("127.0.0.1", p) for p in ports])
    if os.environ.get("WAVE_DIST_PLANE") == "0":
        # the runner attaches a DeviceDataPlane by default on TCP
        # transports; this opts back into host-byte exchanges
        from parsec_tpu.utils.params import params
        params.set_cmdline("wave_dist_plane", "off")
    try:
        coll = TwoDimBlockCyclic(n, n, nb, nb, dtype=dtype, P=nb_ranks,
                                 Q=1, nodes=nb_ranks, rank=rank)
        coll.name = "descA"
        coll.from_numpy(M.copy())
        tp = dpotrf_taskpool(coll, rank=rank, nb_ranks=nb_ranks)
        w = ptg.wave(tp, comm=eng)
        best = None
        for _ in range(reps):
            import jax
            pools = w.build_pools()
            jax.block_until_ready(pools)
            eng.sync()                      # all ranks staged
            t0 = time.perf_counter()
            pools = w.execute(pools)
            jax.block_until_ready(pools)
            dt = time.perf_counter() - t0
            eng.sync()
            best = dt if best is None else min(best, dt)
        w.scatter_pools(pools)
        # numerics: my owned lower tiles vs a reference Cholesky
        ref = np.linalg.cholesky(M.astype(np.float64))
        err = 0.0
        for (i, j) in coll.tiles():
            if coll.rank_of(i, j) != rank or i < j:
                continue
            t = np.asarray(coll.data_of(i, j).sync_to_host().payload,
                           dtype=np.float64)
            if i == j:
                t = np.tril(t)
            r = ref[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
            scale = max(1.0, float(np.abs(r).max()))
            err = max(err, float(np.abs(t - r).max()) / scale)
        eng.sync()
        print(json.dumps({"rank": rank, "secs": best, "rel_err": err,
                          "msgs": eng.fabric.msg_count,
                          "bytes": eng.fabric.bytes_count}), flush=True)
        return 0
    finally:
        eng.fini()


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--rank":
        return rank_main()
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
    nb = int(sys.argv[2]) if len(sys.argv) > 2 else 512
    np_ = int(sys.argv[3]) if len(sys.argv) > 3 else 2

    from parsec_tpu.comm.tcp import free_ports
    ports = free_ports(np_)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--rank", str(r),
         str(np_), ",".join(map(str, ports)), str(n), str(nb)],
        stdout=subprocess.PIPE, text=True, env=env) for r in range(np_)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=3600)
        if p.returncode != 0:
            for q in procs:
                q.kill()
            raise SystemExit(f"rank failed rc={p.returncode}: {out}")
        outs.append(json.loads(out.strip().splitlines()[-1]))
    secs = max(o["secs"] for o in outs)
    err = max(o["rel_err"] for o in outs)
    flops = n ** 3 / 3.0 + n ** 2 / 2.0
    print(json.dumps({
        "metric": f"dist_wave_dpotrf(N={n},NB={nb},ranks={np_},tcp)",
        "gflops": round(flops / secs / 1e9, 2),
        "secs": round(secs, 3),
        "rel_err": err,
        "numerics_ok": err < 5e-2,
        "wire_bytes": sum(o["bytes"] for o in outs),
        "wire_msgs": sum(o["msgs"] for o in outs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
