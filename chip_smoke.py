#!/usr/bin/env python3
"""Chip smoke: the quickest proof that parsec_tpu still starts on the TPU.

    python3 chip_smoke.py [--seed S]

ONE process: it imports JAX once and owns the chip(s) for its whole
life; nothing it starts needs the chip.  It drives the main path a user
gets — ``ops.dpotrf(ctx, A)`` with no knob set, N=16384, NB=512, f32:
5,984 tasks, 1 GiB of matrix through stage-in, the HBM LRU and batched
dispatch — then each other engine, dgeqrf and a DTD burst at N=4096,
checks every result against a float64 host reference that shares no
code with the runtime, and asserts from the device counters that no
fast rung quietly gave way to a slower one.  On a four-chip host it
also proves every chip worked and runs the mesh and 2x2-rank
arrangements.  It fails at once unless JAX's default backend is a TPU
and the native core built.

The last line of stdout on success is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
leg means a non-zero exit and no such line.

``--rehearse`` (sandbox: tiny sizes, CPU allowed, every line marked
REHEARSAL) and ``--only LEG,...`` (spend chip minutes on one leg) can
never print the pass line.  Timings printed here are observations for
whoever builds the benchmark, not benchmark numbers.
"""
import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

#: ||L(L^T x) - M x||_2 / ||M x||_2 over three seeded x.  A true f32
#: Cholesky of this matrix sits near 1e-6; one-pass bf16 MXU inputs
#: (JAX's default matmul precision on TPU) near 2e-3 (ROADMAP S4: the
#: diagonal is ~N and bf16 keeps 8 bits of it).  1e-4 passes the former
#: and fails the latter, so a silently lowered precision cannot pass.
DPOTRF_TOL = 1e-4
#: max|R^T R - A^T A| / max|A^T A| (Q is discarded by design, so the
#: normal-equations identity is the factor check, as in perfbench's
#: dgeqrf cells).
#: Householder QR in f32 at N=4096 lands near 1e-6 (error ~ sqrt(N) eps
#: against entries ~N/4); bf16 inputs near 4e-3.  Same 1e-4 divide.
DGEQRF_TOL = 1e-4

_PREFIX = ""


def say(msg=""):
    print(f"{_PREFIX}{msg}", flush=True)


class SmokeFailure(Exception):
    pass


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------- #
# inputs and the host reference (no runtime code below this banner)      #
# ---------------------------------------------------------------------- #
def make_spd_input(n, seed):
    """Symmetric + N*I in O(N^2) on the host (a Gram-matrix SPD would be
    O(N^3) in float64 and dominate the run at N=16384)."""
    rng = np.random.default_rng(seed)
    B = rng.random((n, n), dtype=np.float32) - np.float32(0.5)
    M = (B + B.T) * np.float32(0.5)
    M[np.diag_indices(n)] += np.float32(n)
    return M


def dpotrf_residual(factor, M, seed):
    """max over three seeded vectors of ||L(L^T x) - M x|| / ||M x||,
    float64 on the host."""
    n = M.shape[0]
    L = np.tril(factor).astype(np.float64)
    X = np.random.default_rng(seed + 1).standard_normal((n, 3))
    ref = M.astype(np.float64) @ X
    got = L @ (L.T @ X)
    return float((np.linalg.norm(got - ref, axis=0)
                  / np.linalg.norm(ref, axis=0)).max())


def dgeqrf_residual(factor, M):
    R = np.triu(factor).astype(np.float64)
    G = M.astype(np.float64)
    G = G.T @ G
    return float(np.abs(R.T @ R - G).max() / np.abs(G).max())


# ---------------------------------------------------------------------- #
# device bookkeeping                                                     #
# ---------------------------------------------------------------------- #
DOWNGRADE_KEYS = ("batch_downgrades", "donate_retries", "mesh_downgrades")


def accel_devices(ctx):
    return [d for d in ctx.devices if d.device_type == "tpu"]


def stats_sum(devs, key):
    return sum(d.stats.get(key, 0) for d in devs)


def snapshot(devs):
    return [dict(d.stats) for d in devs]


def delta(devs, before, key):
    return sum(d.stats.get(key, 0) - b.get(key, 0)
               for d, b in zip(devs, before))


def require_no_downgrade(devs, leg):
    for key in DOWNGRADE_KEYS:
        n = stats_sum(devs, key)
        require(n == 0, f"{leg}: {key}={n} (a fast rung gave way; see "
                        f"the warning above for the exception)")


def chips_of(dev):
    """The jax devices behind one runtime device (a mesh device spans
    several)."""
    return list(getattr(dev, "chips", None) or [dev.jax_device])


def peak_bytes(jdev):
    return int((jdev.memory_stats() or {}).get("peak_bytes_in_use", 0))


def report_devices(devs):
    for d in devs:
        say(f"  {d.name}: stats={d.stats}")
        say(f"  {d.name}: mem_highwater={d.mem_highwater} "
            f"mem_budget={d.mem_budget} peak_bytes_in_use="
            f"{[peak_bytes(c) for c in chips_of(d)]}")


def block_on_tiles(A):
    """End of every timed region: ``wait()`` returns at dispatch
    (dependencies release there), the work is done when every tile's
    newest copy is ready."""
    import jax
    jax.block_until_ready([A.data_of(*c).newest_copy().payload
                           for c in A.tiles()])


def n_dpotrf_tasks(nt):
    return nt * (nt + 1) * (nt + 2) // 6


# ---------------------------------------------------------------------- #
# legs                                                                   #
# ---------------------------------------------------------------------- #
def leg_main(cfg):
    """The path a user gets: init(), from_numpy, ops.dpotrf, no knob."""
    import jax
    import parsec_tpu
    from parsec_tpu import ops
    from parsec_tpu.collections import TwoDimBlockCyclic

    n, nb = cfg.n_main, cfg.nb
    nt = n // nb
    want = n_dpotrf_tasks(nt)
    t0 = time.perf_counter()
    M = make_spd_input(n, cfg.seed)
    say(f"main: input N={n} NB={nb} float32 built in "
        f"{time.perf_counter() - t0:.1f}s (NT={nt}, {want} tasks)")
    ctx = parsec_tpu.init()
    try:
        devs = accel_devices(ctx)
        require(devs, "main: init() attached no accelerator device")
        say(f"main: devices {[d.name for d in ctx.devices]}, "
            f"{ctx.nb_cores} worker threads")
        if not cfg.rehearse:
            bad = [d.name for d in devs
                   if any(c.platform != "tpu" for c in chips_of(d))]
            require(not bad, f"main: accelerator devices not on a TPU: "
                             f"{bad}")
        walls = {}
        A = None
        for label in ("cold", "warm"):
            A = TwoDimBlockCyclic(n, n, nb, nb,
                                  dtype=np.float32).from_numpy(M)
            before = snapshot(devs)
            t0 = time.perf_counter()
            ops.dpotrf(ctx, A)
            block_on_tiles(A)
            walls[label] = time.perf_counter() - t0
            run = {k: delta(devs, before, k) for k in (
                "tasks", "batches", "batched_tasks", "stage_in_bytes",
                "stage_out_bytes", "evictions", "dispatch_ns")}
            say(f"main: {label} wall {walls[label]:.3f}s = "
                f"{walls[label] * 1e6 / want:.1f} us/task; {run}")
            require(run["tasks"] == want,
                    f"main({label}): {run['tasks']} tasks ran on "
                    f"accelerator devices, expected {want}")
            require(run["batches"] > 0, f"main({label}): batched "
                                        f"dispatch never engaged")
            # nothing may leave the chip before the factor is pulled
            # (1 GiB of tiles fits HBM many times over)
            require(run["stage_out_bytes"] == 0,
                    f"main({label}): {run['stage_out_bytes']} bytes "
                    f"staged out during the factorization")
        require_no_downgrade(devs, "main")
        report_devices(devs)
        if len(jax.local_devices()) == 4 and not ctx.device_mesh:
            # code that has never seen more than one real chip may put
            # everything on the first
            for d in devs:
                require(d.stats["tasks"] > 0,
                        f"main: {d.name} executed no task")
                require(cfg.rehearse or peak_bytes(d.jax_device) > 0,
                        f"main: {d.name} never held a byte")
        t0 = time.perf_counter()
        factor = A.to_numpy()
        say(f"main: factor pulled to host in "
            f"{time.perf_counter() - t0:.1f}s")
    finally:
        ctx.fini()
    check_dpotrf("main", cfg, factor, M)
    cfg.facts.update(main_tasks=want, main_cold_wall_s=walls["cold"],
                     main_warm_wall_s=walls["warm"])


def _engine_input(cfg):
    if cfg.M_engine is None:
        cfg.M_engine = make_spd_input(cfg.n_engine, cfg.seed + 10)
    return cfg.M_engine


def _tiled(cfg, M, **kw):
    from parsec_tpu.collections import TwoDimBlockCyclic
    n = M.shape[0]
    return TwoDimBlockCyclic(n, n, cfg.nb, cfg.nb, dtype=np.float32,
                             **kw).from_numpy(M)


def check_dpotrf(leg, cfg, factor, M):
    res = dpotrf_residual(factor, M, cfg.seed)
    say(f"{leg}: residual {res:.3e} (bound {DPOTRF_TOL:g})")
    require(res <= DPOTRF_TOL, f"{leg}: residual {res:.3e} > {DPOTRF_TOL}")


def _ctx_dpotrf_leg(leg, cfg, overrides, check):
    """One dpotrf through a Context at the engine size under
    ``overrides`` (MCA knob -> value); ``check(ctx, tp, devs)`` asserts
    that the engine the leg names is the one that ran."""
    from contextlib import ExitStack

    import parsec_tpu
    from parsec_tpu.ops import dpotrf_taskpool
    from parsec_tpu.utils.params import params

    M = _engine_input(cfg)
    with ExitStack() as st:
        for k, v in overrides.items():
            st.enter_context(params.cmdline_override(k, v))
        ctx = parsec_tpu.init()
        try:
            devs = accel_devices(ctx)
            A = _tiled(cfg, M)
            tp = dpotrf_taskpool(A)
            t0 = time.perf_counter()
            ctx.add_taskpool(tp)
            ctx.wait()
            block_on_tiles(A)
            say(f"{leg}: {overrides} cold wall "
                f"{time.perf_counter() - t0:.3f}s "
                f"({n_dpotrf_tasks(A.nt)} tasks)")
            check(ctx, tp, devs)
            require_no_downgrade(devs, leg)
            check_dpotrf(leg, cfg, A.to_numpy(), M)
        finally:
            ctx.fini()


def leg_turbo(cfg):
    def check(ctx, tp, devs):
        require(tp._turbo is not None,
                "turbo: the native loop did not take the pool "
                "(ptg_dep_management=static fell back to classic)")
        say(f"turbo: stats {tp._turbo.stats}")
    _ctx_dpotrf_leg("turbo", cfg, {"ptg_dep_management": "static"}, check)


def leg_stagec(cfg):
    def check(ctx, tp, devs):
        s = ctx.stage_stats
        say(f"stagec: stage_stats {s}")
        require(s["stage_dispatches"] > 0, "stagec: no stage dispatched")
        for key in ("stage_fallbacks", "chain_fallbacks"):
            require(s[key] == 0, f"stagec: {key}={s[key]}")
    _ctx_dpotrf_leg("stagec", cfg, {"stage_compile": "1"}, check)


def _runner_leg(leg, cfg, make):
    """wave / capture: whole-DAG engines that need no Context."""
    from parsec_tpu.ops import dpotrf_taskpool
    M = _engine_input(cfg)
    A = _tiled(cfg, M)
    runner = make(dpotrf_taskpool(A))
    t0 = time.perf_counter()
    runner.run()
    say(f"{leg}: cold wall {time.perf_counter() - t0:.3f}s "
        f"({runner.nb_tasks} tasks)")
    check_dpotrf(leg, cfg, A.to_numpy(), M)


def leg_wave(cfg):
    from parsec_tpu.dsl import ptg
    _runner_leg("wave", cfg, ptg.wave)


def leg_capture(cfg):
    from parsec_tpu.dsl import ptg
    _runner_leg("capture", cfg, ptg.capture)


def leg_dgeqrf(cfg):
    """Second workload through the same default path."""
    import parsec_tpu
    from parsec_tpu import ops

    n = cfg.n_engine
    M = np.random.default_rng(cfg.seed + 20).random(
        (n, n), dtype=np.float32)
    ctx = parsec_tpu.init()
    try:
        devs = accel_devices(ctx)
        A = _tiled(cfg, M)
        t0 = time.perf_counter()
        ops.dgeqrf(ctx, A)
        block_on_tiles(A)
        say(f"dgeqrf: N={n} NB={cfg.nb} cold wall "
            f"{time.perf_counter() - t0:.3f}s; tasks="
            f"{stats_sum(devs, 'tasks')} batches="
            f"{stats_sum(devs, 'batches')}")
        require(stats_sum(devs, "tasks") > 0,
                "dgeqrf: no task ran on an accelerator device")
        require_no_downgrade(devs, "dgeqrf")
        factor = A.to_numpy()
    finally:
        ctx.fini()
    res = dgeqrf_residual(factor, M)
    say(f"dgeqrf: residual {res:.3e} (bound {DGEQRF_TOL:g})")
    require(res <= DGEQRF_TOL, f"dgeqrf: residual {res:.3e} > {DGEQRF_TOL}")


def leg_dtd(cfg):
    """A burst of same-class DTD inserts with a "tpu" chore: the stacked
    path must take them, and x*2*10 is exact in f32."""
    import parsec_tpu
    from parsec_tpu import dtd
    from parsec_tpu.dsl.dtd import INOUT, VALUE, unpack_args

    burst, nb = cfg.dtd_burst, cfg.nb
    ctx = parsec_tpu.init()
    try:
        devs = accel_devices(ctx)
        tp = dtd.taskpool_new()
        ctx.add_taskpool(tp)

        def scale(es, task):
            x, a = unpack_args(task)
            x *= a

        # the class and its chore ahead of the inserts: no task can
        # reach a worker without its accelerator incarnation
        tc = tp.create_task_class("scale", 1, scale)
        tp.add_chore(tc, "tpu", lambda x, a: x * a)
        tiles = [tp.tile_of_array(np.full((nb, nb), i + 1, np.float32))
                 for i in range(burst)]
        t0 = time.perf_counter()
        for a in (2.0, 10.0):
            for t in tiles:
                tp.insert_task_with_task_class(tc, (t, INOUT), (a, VALUE))
        tp.data_flush_all()
        tp.wait()
        wall = time.perf_counter() - t0
        say(f"dtd: {2 * burst} inserts on {nb}x{nb} tiles in {wall:.3f}s; "
            f"tasks={stats_sum(devs, 'tasks')} "
            f"batches={stats_sum(devs, 'batches')} "
            f"batched_tasks={stats_sum(devs, 'batched_tasks')}")
        require(stats_sum(devs, "tasks") == 2 * burst,
                f"dtd: {stats_sum(devs, 'tasks')} tasks on accelerator "
                f"devices, expected {2 * burst}")
        require(stats_sum(devs, "batches") > 0,
                "dtd: batched dispatch never engaged")
        require_no_downgrade(devs, "dtd")
        for i, t in enumerate(tiles):
            got = np.asarray(t.data.sync_to_host().payload)
            require(np.array_equal(
                got, np.full((nb, nb), 20.0 * (i + 1), np.float32)),
                f"dtd: tile {i} is not exactly {20.0 * (i + 1)}")
    finally:
        ctx.fini()


def leg_host(cfg):
    """Host facts (ROADMAP S6): observations, not benchmark numbers."""
    import jax
    import jax.numpy as jnp

    dev = jax.local_devices()[0]
    tiny = jax.jit(lambda x: x + 1.0)
    x = jax.device_put(np.zeros((8, 128), np.float32), dev)
    tiny(x).block_until_ready()
    lat = []
    for _ in range(200):
        t0 = time.perf_counter()
        tiny(x).block_until_ready()
        lat.append(time.perf_counter() - t0)
    cfg.facts["call_latency_us_median"] = float(np.median(lat) * 1e6)
    say(f"host: sync latency of a tiny jitted call: median "
        f"{np.median(lat) * 1e6:.1f} us, p90 "
        f"{np.percentile(lat, 90) * 1e6:.1f} us (200 calls)")

    mb = cfg.link_mb
    buf = np.random.default_rng(cfg.seed).random(
        mb * (1 << 18), dtype=np.float32)
    h2d, d2h = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        xd = jax.device_put(buf, dev)
        xd.block_until_ready()
        h2d.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        back = np.asarray(xd)
        d2h.append(time.perf_counter() - t0)
    require(np.array_equal(back, buf), "host: D2H(H2D(x)) != x")
    cfg.facts["h2d_mb_per_s_median"] = mb / float(np.median(h2d))
    cfg.facts["d2h_mb_per_s_median"] = mb / float(np.median(d2h))
    say(f"host: {mb} MB H2D median {mb / np.median(h2d):.0f} MB/s, "
        f"D2H median {mb / np.median(d2h):.0f} MB/s (5 each)")

    # is block_until_ready honest?  A chain of K dependent GEMMs timed
    # to block_until_ready, then again with a one-element D2H of the
    # result after it: an early return would show as a pull that takes
    # as long as the chain.
    n, k = cfg.n_gemm, 16
    g = jax.jit(lambda a: jnp.dot(a, a) * (1.0 / n))
    a0 = jax.device_put(np.random.default_rng(cfg.seed).random(
        (n, n), dtype=np.float32), dev)
    g(a0).block_until_ready()
    blocks, pulls = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        y = a0
        for _ in range(k):
            y = g(y)
        y.block_until_ready()
        t1 = time.perf_counter()
        float(np.asarray(y[0, 0]))
        t2 = time.perf_counter()
        blocks.append(t1 - t0)
        pulls.append(t2 - t1)
    cfg.facts["gemm_chain_block_s_median"] = float(np.median(blocks))
    cfg.facts["gemm_chain_pull_after_s_median"] = float(np.median(pulls))
    say(f"host: {k} chained {n}^3 f32 GEMMs to block_until_ready: median "
        f"{np.median(blocks) * 1e3:.2f} ms; one-element pull after it: "
        f"median {np.median(pulls) * 1e3:.3f} ms "
        f"(honest when the pull is a small fraction of the chain)")


def leg_mesh(cfg):
    """Four chips as ONE mesh device (device_mesh_shape=2x2)."""
    def check(ctx, tp, devs):
        require(ctx.device_mesh is not None, "mesh: no mesh device built")
        report_devices(devs)
        require(stats_sum(devs, "mesh_dispatches") > 0,
                "mesh: no flush group went through shard_map")
        require(stats_sum(devs, "tasks") == n_dpotrf_tasks(
            cfg.n_engine // cfg.nb), "mesh: task count is off")
    _ctx_dpotrf_leg("mesh", cfg, {"device_mesh_shape": "2x2"}, check)


def leg_ranks(cfg):
    """Four in-process ranks on a 2x2 block-cyclic grid (ROADMAP R1's
    four-chip shape).  build_devices binds a rank to chips only in the
    mesh case, so each plain rank attaches all four chips."""
    import parsec_tpu
    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.ops import dpotrf_taskpool
    from parsec_tpu.utils.spmd import spmd_threads

    M = _engine_input(cfg)
    n, nb, R = cfg.n_engine, cfg.nb, 4

    def rank_fn(r, fab):
        ctx = parsec_tpu.Context(comm=RemoteDepEngine(fab.engine(r)))
        try:
            devs = accel_devices(ctx)
            A = _tiled(cfg, M, P=2, Q=2, nodes=R, rank=r)
            A.name = "descA"
            ctx.add_taskpool(dpotrf_taskpool(A, rank=r, nb_ranks=R))
            ctx.wait()
            require_no_downgrade(devs, f"ranks[{r}]")
            owned = {c: np.asarray(A.data_of(*c).sync_to_host().payload)
                     for c in A.tiles() if A.rank_of(*c) == r}
            return owned, {d.name: d.stats["tasks"] for d in devs}
        finally:
            ctx.fini()

    t0 = time.perf_counter()
    results, _ = spmd_threads(R, rank_fn, timeout=900)
    say(f"ranks: 4 in-process ranks, 2x2 grid, wall "
        f"{time.perf_counter() - t0:.3f}s")
    L = np.zeros((n, n), np.float32)
    total = 0
    for r, (owned, per_chip) in enumerate(results):
        say(f"ranks: rank {r} tasks per chip {per_chip}")
        total += sum(per_chip.values())
        for (m, k), t in owned.items():
            L[m * nb:(m + 1) * nb, k * nb:(k + 1) * nb] = t
    require(total == n_dpotrf_tasks(n // nb),
            f"ranks: {total} tasks over all ranks, expected "
            f"{n_dpotrf_tasks(n // nb)}")
    check_dpotrf("ranks", cfg, L, M)


#: in run order; the last two need four chips
LEGS = (("main", leg_main), ("turbo", leg_turbo), ("stagec", leg_stagec),
        ("wave", leg_wave), ("capture", leg_capture),
        ("dgeqrf", leg_dgeqrf), ("dtd", leg_dtd), ("host", leg_host),
        ("mesh", leg_mesh), ("ranks", leg_ranks))
FOUR_CHIP_LEGS = ("mesh", "ranks")


class Config:
    def __init__(self, args):
        self.seed = args.seed
        self.rehearse = args.rehearse
        if args.rehearse:
            # NT=8 like the real engine legs, so that groups are wide
            # enough to batch (and to shard over four devices)
            self.n_main = self.n_engine = 512
            self.nb, self.dtd_burst = 64, 16
            self.link_mb, self.n_gemm = 4, 256
        else:
            self.n_main, self.n_engine = 16384, 4096
            self.nb, self.dtd_burst = 512, 64
            self.link_mb, self.n_gemm = 64, 4096
        self.M_engine = None
        self.facts = {}


def main():
    global _PREFIX
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox run: tiny sizes, CPU allowed, every "
                         "line marked REHEARSAL, never prints the pass "
                         "line")
    ap.add_argument("--only", default="",
                    help="comma-separated legs to run "
                         f"({','.join(n for n, _ in LEGS)}); a partial "
                         "run never prints the pass line")
    args = ap.parse_args()
    if args.rehearse:
        _PREFIX = "REHEARSAL "
    only = [s for s in args.only.split(",") if s]
    unknown = set(only) - {n for n, _ in LEGS}
    if unknown:
        ap.error(f"--only: unknown leg(s) {sorted(unknown)}")

    import jax
    # f32 tiles mean f32 arithmetic: JAX's TPU default feeds the MXU
    # one bf16 pass, which DPOTRF_TOL is set to fail
    jax.config.update("jax_default_matmul_precision", "highest")
    import parsec_tpu
    from parsec_tpu import native
    from parsec_tpu.utils.compile_cache import ENV_VAR

    mca = {k: v for k, v in os.environ.items()
           if k.startswith("PARSEC_MCA_")}
    say(f"chip_smoke: seed={args.seed} PARSEC_MCA_* in env: {mca or 'none'}")
    say(f"chip_smoke: jax {jax.__version__}, "
        f"jax_default_matmul_precision=highest (set here)")
    backend = jax.default_backend()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"chip_smoke: backend={backend} device={device} "
        f"local_device_count={jax.local_device_count()}")
    say(f"chip_smoke: compile cache {jax.config.jax_compilation_cache_dir}"
        f" ({ENV_VAR} {'set' if os.environ.get(ENV_VAR) else 'not set'})")
    say(f"chip_smoke: native core available={native.available}")
    if backend != "tpu" and not args.rehearse:
        say(f"chip_smoke: FAILED: no TPU: jax.default_backend() is "
            f"{backend!r}; this smoke only runs on the chip "
            f"(--rehearse for a CPU dry run that cannot pass)")
        return 2
    if not native.available:
        say("chip_smoke: FAILED: parsec_tpu.native did not build/import "
            "(g++ error above); turbo would silently not be turbo")
        return 2

    cfg = Config(args)
    four = jax.local_device_count() == 4
    failed, ran = [], []
    t_all = time.perf_counter()
    for name, fn in LEGS:
        if only and name not in only:
            continue
        if name in FOUR_CHIP_LEGS and not four:
            say(f"--- {name}: skipped (needs 4 local chips, have "
                f"{jax.local_device_count()})")
            continue
        say(f"--- {name}")
        t0 = time.perf_counter()
        try:
            fn(cfg)
        except Exception:   # the boundary: record, run the other legs
            failed.append(name)
            say(f"{name}: FAILED after {time.perf_counter() - t0:.1f}s\n"
                f"{traceback.format_exc()}")
        else:
            ran.append(name)
            say(f"{name}: ok in {time.perf_counter() - t0:.1f}s")
    say(f"--- facts: {json.dumps(cfg.facts, sort_keys=True)}")
    say(f"chip_smoke: {len(ran)} leg(s) ok {ran}, {len(failed)} failed "
        f"{failed}, total {time.perf_counter() - t_all:.1f}s")
    if failed:
        return 1
    if args.rehearse or only:
        say("chip_smoke: partial/rehearsal run: no pass line")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
