#!/usr/bin/env python
"""Benchmark driver: PTG tile Cholesky (dpotrf_L) GFLOP/s on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline: the reference repo publishes no numbers (BASELINE.md); the
north-star target is >=60% of an A100-node's per-device dpotrf rate. We
take 15.5 TFLOP/s as the A100-class dpotrf rate (DPLASMA-style dpotrf
sustains ~80% of the A100's 19.5 TFLOP/s FP64-TC peak), making the target
0.6 * 15500 = 9300 GFLOP/s; vs_baseline = measured / 9300.

Execution modes (BENCH_MODE):

- ``all`` (default): the composite — runs {capture_chain@N=32768,
  wave@NB=1024/512, capture, runtime@NB=512, chip_gemm microbench, link
  probe}, emits the headline from the BEST numerics-passing mode and
  keeps every mode in extras.  A leg that raises, or a run in which no
  mode passes numerics, exits non-zero.
- ``chain``: K whole-DAG factorizations inside ONE jitted call, input
  synthesized on device from a PRNG, residual computed on device; only
  scalars cross the host link, so wall time is 1x call latency +
  K x compute.
- ``capture``: the PTG DAG compiled into ONE XLA executable via graph
  capture (dsl/ptg/capture.py) — single dispatch, zero host loop in the
  timed region, MXU-bound.
- ``wave``: lowered DAG as batched per-class XLA calls over device tile
  pools (dsl/ptg/wave.py) — the scalable runtime path at small NB.
- ``runtime``: per-task dispatch through the scheduler/device module
  (the distributed-capable path; bounded by ~0.3 ms/task of Python
  dispatch).
- ``dispatch``: device-module dispatch microbenchmark — a same-class
  64-task burst through the classic runtime, batched (the stacked
  jitted-call pipeline, device_batch_max) vs per-task; reports
  amortized CPU-side dispatch µs/task, wall µs/task, batch occupancy
  and the prefetch hit rate (stage-in overlapped with execution).
- ``overlap``: 2-rank classic-runtime dpotrf on a throttled link
  (injected per-frame delay), overlap pipeline ON (segmented flush +
  remote-GET prefetch + critical-path priorities) vs OFF — reports
  each leg's wall, the live OVERLAP_FRACTION gauge, and bit-exactness
  across legs.
- ``elastic``: elastic grid recovery — cross-grid reshard-restore
  throughput (4-writer snapshot onto a 2-rank grid), and the 3-rank
  kill-mid-dpotrf shrink-recovery wall vs the failure-free run
  (detection + agreement + reshard + replay, no operator in the loop).
- ``stagec``: whole-stage DAG->XLA compilation (ISSUE 12) — the SAME
  classic-runtime dpotrf at the SAME N/NB interpreted vs lowered into
  fused jitted stages (scrubbed CPU subprocess, prestaged tiles,
  bit-exactness gated); reports both GFLOP/s and the speedup.
- ``geqrf``: the second workload — runtime-path tile QR (dgeqrf) with
  the ``R^T R == A^T A`` residual, so it stops rotting silently.
- ``qwire``: quantized wire codecs (ISSUE 14) — the SAME 2-rank
  classic-runtime dpotrf over real loopback TCP on a throttled link,
  lossless vs blockwise-bf16 vs int8-with-scale (scrubbed CPU
  subprocess); reports wall, payload bytes on the wire, per-link
  labeled reduction ratios, residual per leg, and the knob-unset
  bit-identity differential.
- ``trace``: cross-rank flow tracing (ISSUE 15) — the SAME 2-rank
  classic-runtime dpotrf over real loopback TCP on a throttled link,
  ``obs_flow`` off vs on; reports the µs/task delta, the added wire
  bytes per message (the pickled trace context), the stitched
  cross-rank edge counts per direction, the min offset-corrected
  send→recv lag, and the knob-unset wire byte-capture differential
  (a scripted deterministic exchange captured at the frame level must
  be BIT-IDENTICAL with the knob unset, and toward a peer that never
  advertised "tr").
- ``health``: streaming health monitor (ISSUE 16) — the SAME 2-rank
  throttled-TCP dpotrf, ``obs_live`` off vs on (µs/task overhead of
  the online span folding + window ticks), plus detector latency: one
  clean dpotrf warms the baselines, then rank 1's fault injector is
  swapped mid-run to a 4x send delay and the time until rank 0's
  straggler/degraded-link detector fires on the inbound link is
  reported (kind, link, suspect ride along).
- ``serve``: multi-tenant serving (ISSUE 18) — a weight-8 latency
  tenant probing one persistent context a weight-1 bulk tenant
  saturates, weighted-fair deficit boosts ON vs pure FIFO (scrubbed
  CPU subprocess); reports per-tenant p50/p99 pool latency for both
  legs, the weighted/FIFO p99 ratio, and the tenants' completed-pool
  share.  The serve-knob wire differential (a ``serve``-on rank's data
  frames toward a knob-unset peer must be bit-identical to the unset
  legs) rides the ``trace`` capture-identity differential.
- ``dplane``: device-plane transport + redistribution planner (ISSUE
  19) — the SAME whole-matrix P x 1 -> 1 x Q reshard over real TCP
  engines three ways (per-tile DTD GET storm; ``xfer_collective_
  redist`` planned alltoall rounds; planned + ``xfer_dplane`` with the
  loopback transfer backend carrying the bulk payload off the session
  wire), scrubbed CPU subprocess; reports per-leg wall / host-wire
  bytes / MB/s, round+transfer counts vs the per-tile move count,
  bit-identity across all legs, and the two-level vs flat lane-reduce
  timing at equal codec semantics.

Every record carries ``schema_version`` + stable ``metric_id``/``mode``
/``n``/``nb``/``dtype`` fields (schema 2): r01-r05 changed metric
definitions, so cross-run ``vs_baseline`` is only comparable at equal
(schema_version, metric_id, n, nb, dtype).

Knobs (env): BENCH_N (default 8192), BENCH_NB (2048), BENCH_DTYPE
(float32), BENCH_REPS (3, best-of), BENCH_CORES (runtime mode worker
threads, default 1: eager completion makes one thread the fastest driver
on a single-CPU-core host), BENCH_CHAIN_N (32768) / BENCH_CHAIN_NB
(2048) / BENCH_CHAIN_K (4) for the chain mode. Input staging and
verification never cross the host link in the XLA modes (on-device
synthesis + device-side residuals).

The modes that time the chip (all, chain, capture, wave, runtime,
geqrf) refuse to run unless ``jax.default_backend()`` is a TPU: a CPU
timing is not a chip number.  The CPU-subprocess legs run anywhere.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

BASELINE_GFLOPS = 9300.0


def make_input(n, dtype):
    # O(N^2) SPD construction (symmetric + strictly diagonally dominant);
    # a Gram-matrix form would be O(N^3) on the host and dominate wall time
    rng0 = np.random.RandomState(0)
    B = rng0.rand(n, n) - 0.5
    return ((B + B.T) / 2 + n * np.eye(n)).astype(dtype)


def check_numerics(L_np, M, n):
    # O(N^2) residual ||L(L^T x) - M x|| / ||M x|| on random vectors so
    # verification does not dwarf the timed region at large N
    L = np.tril(L_np).astype(np.float64)
    rng = np.random.RandomState(0)
    X = rng.rand(n, 4)
    ref = M.astype(np.float64) @ X
    return float(np.abs(L @ (L.T @ X) - ref).max() / np.abs(ref).max())


def check_numerics_device(tile_map, M, n, nb):
    """Same residual computed ON DEVICE from the factored tiles: only
    scalars cross the host link, so verification adds no bulk D2H of
    the factor to the run it gates."""
    import jax
    import jax.numpy as jnp

    coords = sorted(tile_map)
    tiles = [tile_map[c] for c in coords]

    def resid(ts, ref, X):
        L = jnp.zeros((n, n), ts[0].dtype)
        for (m, k), t in zip(coords, ts):
            if m == k:
                t = jnp.tril(t)
            # slice extents from the tile's true shape (ragged tilings:
            # edge tiles are lm%nb short)
            L = L.at[m * nb:m * nb + t.shape[0],
                     k * nb:k * nb + t.shape[1]].set(t)
        return jnp.abs(L @ (L.T @ X) - ref).max() / jnp.abs(ref).max()

    rng = np.random.RandomState(0)
    Xh = rng.rand(n, 4).astype(np.float32)
    # the reference product M @ X is O(N^2) on the HOST: uploading M
    # itself would be another N x N bulk transfer — the thing this
    # function exists to avoid
    refh = (M.astype(np.float64) @ Xh).astype(np.float32)
    X = jax.device_put(Xh)
    ref = jax.device_put(refh)
    return float(jax.jit(resid)(tiles, ref, X))


NUMERICS_TOL = 5e-2


def dpotrf_flops(n):
    return n ** 3 / 3.0 + n ** 2 / 2.0


def _synth_lower(key, nt, nb, n, jdt):
    """Lower tiles of A = (B + B^T)/2 + n*I synthesized on device from a
    folded PRNG key, tile-wise — the full matrix never materializes and
    nothing crosses the host link (zero-H2D input path)."""
    import jax.numpy as jnp
    from jax import random
    tiles = {}
    for m in range(nt):
        for k in range(m + 1):
            bmk = random.uniform(random.fold_in(key, m * nt + k),
                                 (nb, nb), jnp.float32)
            t = (bmk + random.uniform(random.fold_in(key, k * nt + m),
                                      (nb, nb), jnp.float32).T) * 0.5
            if m == k:
                t = t + n * jnp.eye(nb, dtype=jnp.float32)
            tiles[(m, k)] = t.astype(jdt)
    return tiles


def synth_spd_pool_fn(key, nt, nb, n, jdt):
    """Whole-pool SPD synthesis for WaveRunner.synth_pools(pool_fn=):
    same tile values as _synth_lower (B[m,k] = uniform(fold_in(key,
    m*nt+k)); A = (B+B^T)/2 + n*I on the diagonal; upper tiles zero)
    but built one block-ROW at a time with vmapped PRNG inside a
    fori_loop, so the traced program is O(nt), not O(nt^2) (the
    per-tile form at NT=64 emits a 360 KB MLIR module)."""
    import jax.numpy as jnp
    from jax import lax, random, vmap

    def pool_fn(_name, coords):
        # coords may be a SUBSET of the square (uplo/shape-split
        # pools): absent coords map to an out-of-bounds row and the
        # scatter drops them instead of clobbering row 0
        pos = np.full((nt, nt), len(coords), np.int32)
        for i, (m, k) in enumerate(coords):
            pos[m, k] = i
        pos_j = jnp.asarray(pos)
        kgrid = jnp.arange(nt)
        eye = n * jnp.eye(nb, dtype=jnp.float32)

        def gen_row(m):
            ka = vmap(lambda k: random.fold_in(key, m * nt + k))(kgrid)
            kb = vmap(lambda k: random.fold_in(key, k * nt + m))(kgrid)
            A = vmap(lambda kk: random.uniform(kk, (nb, nb)))(ka)
            Bt = vmap(lambda kk: random.uniform(kk, (nb, nb)))(kb)
            row = (A + jnp.transpose(Bt, (0, 2, 1))) * 0.5
            row = jnp.where((kgrid == m)[:, None, None], row + eye, row)
            row = jnp.where((kgrid <= m)[:, None, None], row, 0.0)
            return row.astype(jdt)

        def body(m, out):
            return out.at[pos_j[m]].set(gen_row(m), mode="drop")

        init = jnp.zeros((len(coords), nb, nb), jdt)
        return lax.fori_loop(0, nt, body, init)

    return pool_fn


def _synth_ref(low, X, nt, jdt):
    """ref_m = sum_k M[m,k] @ X_k from lower tiles only (symmetry)."""
    return [sum((low[(m, k)] if k <= m else low[(k, m)].T.astype(jdt))
                @ X[k] for k in range(nt)) for m in range(nt)]


def _resid_blocks(tril, X, ref, nt):
    """max-norm residual ||L(L^T X) - ref|| / ||ref|| block-wise from
    factored lower tiles; returns a scalar, no N^2 reconstruction."""
    import jax.numpy as jnp
    y = [sum(tril[(m, k)].T @ X[m] for m in range(k, nt))
         for k in range(nt)]
    num, den = jnp.float32(0), jnp.float32(0)
    for m in range(nt):
        z = sum(tril[(m, k)] @ y[k] for k in range(m + 1))
        num = jnp.maximum(num, jnp.abs(z - ref[m]).max())
        den = jnp.maximum(den, jnp.abs(ref[m]).max())
    return num / den


def bench_capture_chain(n, nb, reps, dtype, chain_k):
    """K whole-DAG factorizations inside ONE jitted XLA call — input
    synthesis, the captured dpotrf DAG, and the residual all run on
    device; only two scalars ever cross the host link.

    Total wall time is 1 x call latency + K x compute, so the host
    loop's per-call cost stays out of the number. The SPD input is synthesized
    per-iteration from a folded PRNG key (A = (B + B^T)/2 + n*I,
    tile-wise — full matrix never materializes), so zero H2D staging;
    the residual ||L(L^T X) - A X|| / ||A X|| is computed block-wise
    from the factored tiles (no N^2 reconstruction) and max-reduced
    across iterations, so a single scalar gates numerics for all K.
    Ref: the watchdog-gate timing pattern of
    /root/reference/tests/dsl/dtd/dtd_test_simple_gemm.c:651-660."""
    import jax
    import jax.numpy as jnp
    from jax import lax, random
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.dsl import ptg
    from parsec_tpu.ops import dpotrf_taskpool

    if n % nb:
        raise ValueError(
            f"chain/capture bench modes use uniform tilings (N={n} % "
            f"NB={nb} != 0); ragged tilings are exercised by the wave "
            f"engine tests (tests/test_ptg_wave.py) and dryrun gate")
    nt = n // nb
    jdt = jnp.dtype(dtype)
    # structure-only collection: tiles are lazy (matrix.py:43) and the
    # captured _execute only touches coords its deps name (the lower
    # triangle), so no host tile is ever allocated
    A = TwoDimBlockCyclic(n, n, nb, nb, dtype=dtype)
    cg = ptg.capture(dpotrf_taskpool(A))
    nvec = 4

    def body(i, carry):
        maxerr, acc = carry
        key = random.fold_in(random.PRNGKey(17), i)
        low = _synth_lower(key, nt, nb, n, jdt)
        X = random.normal(random.fold_in(key, nt * nt), (nt, nb, nvec),
                          jnp.float32)
        ref = _synth_ref(low, X, nt, jdt)
        out = cg._execute({"descA": low})["descA"]
        tril = {c: (jnp.tril(t) if c[0] == c[1] else t)
                for c, t in out.items()}
        err = _resid_blocks(tril, X, ref, nt)
        return (jnp.maximum(maxerr, err),
                acc + tril[(nt - 1, nt - 1)][0, 0])

    @jax.jit
    def chained(j0):
        return lax.fori_loop(j0, j0 + chain_k, body,
                             (jnp.float32(0), jnp.float32(0)))

    err, acc = chained(0)   # compile + first window (untimed)
    jax.block_until_ready([err, acc])
    best = None
    for r in range(reps):
        t0 = time.perf_counter()
        err, acc = chained(r * chain_k)
        jax.block_until_ready([err, acc])
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best / chain_k, float(err)


#: BENCH record schema (ISSUE 12 satellite): r01-r05 changed metric
#: definitions (capture vs wave vs capture_chain), so the legacy
#: "metric" string is NOT comparable across runs.  From schema 2 every
#: record carries STABLE fields — ``schema_version``, ``metric_id``
#: (mode-stable, e.g. "dpotrf_gflops/runtime"), ``mode``, ``n``,
#: ``nb``, ``dtype`` — and cross-run ``vs_baseline`` comparisons must
#: key on (schema_version, metric_id) at equal (n, nb, dtype).
BENCH_SCHEMA_VERSION = 2


def emit_json(rec: dict) -> None:
    """Every BENCH json line goes through here: stamps the schema
    version so downstream diffing can refuse to compare records whose
    metric definitions differ."""
    rec.setdefault("schema_version", BENCH_SCHEMA_VERSION)
    print(json.dumps(rec))


def emit_line(n, nb, dtype, mode, gflops, extras=None):
    line = {
        "metric": f"dpotrf_gflops(N={n},NB={nb},{dtype.name},1chip,{mode})",
        "metric_id": f"dpotrf_gflops/{mode}",
        "mode": mode, "n": n, "nb": nb, "dtype": dtype.name,
        "value": round(gflops, 2),
        "unit": "GFLOP/s",
        "vs_baseline": round(gflops / BASELINE_GFLOPS, 4),
    }
    if extras:
        line["extras"] = extras
    emit_json(line)


def emit(n, nb, dtype, mode, best, err, extras=None):
    if err > NUMERICS_TOL:
        emit_json({"metric": "dpotrf_gflops",
                   "metric_id": f"dpotrf_gflops/{mode}", "mode": mode,
                   "n": n, "nb": nb, "dtype": dtype.name,
                   "value": 0.0, "unit": "GFLOP/s", "vs_baseline": 0.0,
                   "error": f"numerics failed: {err}"})
        return
    emit_line(n, nb, dtype, mode, dpotrf_flops(n) / best / 1e9, extras)


def bench_capture(n, nb, reps, dtype):
    """Whole-DAG XLA execution: one captured executable per shape
    (a chain of length 1 — synthesis + DAG + residual in one call)."""
    return bench_capture_chain(n, nb, reps, dtype, 1)


def bench_wave(n, nb, reps, dtype):
    """Wave execution: ready antichains as batched per-class XLA calls
    over device tile pools (dsl/ptg/wave.py) — the runtime path that
    stays scalable at small NB where per-task dispatch would dominate.
    Pools are synthesized ON DEVICE (no 256 MB H2D staging); the
    timed region is wave execution."""
    import jax
    import jax.numpy as jnp
    from jax import random
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.dsl.ptg.wave import wave
    from parsec_tpu.ops import dpotrf_taskpool

    if n % nb:
        raise ValueError(f"bench wave mode uses uniform tilings "
                         f"(N={n} % NB={nb} != 0)")
    nt = n // nb
    jdt = jnp.dtype(dtype)
    A = TwoDimBlockCyclic(n, n, nb, nb, dtype=dtype)   # tiles stay lazy
    w = wave(dpotrf_taskpool(A),
             max_chunk=int(os.environ.get("BENCH_WAVE_CHUNK", "256")))
    nvec = 4
    key = random.PRNGKey(23)

    pool_fn = synth_spd_pool_fn(key, nt, nb, n, jdt)

    def synth():
        return w.synth_pools(pool_fn=pool_fn)

    def resid(pools):
        loc = w._pool_of["descA"]
        tril = {}
        for (m, k), (pid, row) in loc.items():
            if m >= k:
                t = pools[pid][row]
                tril[(m, k)] = jnp.tril(t) if m == k else t
        X = random.normal(random.fold_in(key, nt * nt), (nt, nb, nvec),
                          jnp.float32)
        ref = _synth_ref(_synth_lower(key, nt, nb, n, jdt), X, nt, jdt)
        return _resid_blocks(tril, X, ref, nt)

    resid_j = jax.jit(resid)
    pools = w.execute(synth())      # warm the kernel cache
    jax.block_until_ready(pools)
    best = None
    for _ in range(reps):
        pools = synth()
        jax.block_until_ready(pools)
        t0 = time.perf_counter()
        pools = w.execute(pools)
        jax.block_until_ready(pools)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best, float(resid_j(pools))


#: per-mode side facts picked up by bench_all into extras (e.g. the
#: CPU-side dispatch rate)
_MODE_NOTES = {}


def bench_runtime(n, nb, reps, cores, dtype, dispatch="turbo"):
    """Per-task dispatch through the context (ctx.add_taskpool + wait).

    dispatch="turbo" (default): static dep management — the lowered DAG
    runs on the native C select/release loop with precompiled slot
    binding, one XLA call per task (dsl/ptg/turbo.py; the reference's
    index-array mode + scheduling.c hot loop). dispatch="classic":
    dynamic hash dep tracking + scheduler + device module per task (the
    historical runtime_gflops path, kept as runtime_classic in extras).
    """
    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.ops import dpotrf_taskpool, make_spd
    from parsec_tpu.utils.params import params

    M = make_input(n, dtype)
    if dispatch == "turbo":
        # drive the TurboRunner directly so pool staging (the H2D of
        # the whole matrix) happens OUTSIDE the clock, mirroring how
        # the classic path's HBM prestage is untimed: the timed region
        # is per-task dispatch + kernels only (steady-state model)
        import jax
        from parsec_tpu.dsl.ptg.turbo import TurboRunner
        from parsec_tpu.collections import TwoDimBlockCyclic as TDBC
        from parsec_tpu.ops import dpotrf_taskpool as mk_tp

        params.set_cmdline("ptg_dep_management", "static")
        try:
            dev = jax.devices()[0]
            best = None
            best_disp = None
            A = r = None
            for _ in range(max(2, reps)):
                A = TDBC(n, n, nb, nb, dtype=dtype).from_numpy(M)
                r = TurboRunner(mk_tp(A))
                pools = r.build_pools(device=dev)
                jax.block_until_ready(pools)
                t0 = time.perf_counter()
                pools = r.execute_per_task(pools, device=dev)
                jax.block_until_ready(pools)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
                ds = r.stats["dispatch_secs"]
                best_disp = ds if best_disp is None else min(best_disp, ds)
            # the CPU-side submission rate: turbo's own cost
            _MODE_NOTES["runtime"] = {
                "turbo_dispatch_us_per_task": round(
                    best_disp * 1e6 / r.dag.n_tasks, 1),
                "turbo_tasks": int(r.dag.n_tasks),
                "turbo_aot_prebound": not hasattr(
                    r._entries[0][0], "lower"),
            }
            # shape-split (pool, row) map for the device-side check
            loc = r._pool_of.get("descA") or next(iter(r._pool_of.values()))
            lower = {c: pools[pid][row] for c, (pid, row) in loc.items()
                     if c[0] >= c[1]}
            return best, check_numerics_device(lower, M, n, nb)
        finally:
            params.unset_cmdline("ptg_dep_management")
    ctx = parsec_tpu.init(nb_cores=cores)
    try:
        # warmup: 3x3 tiles so POTRF/TRSM/SYRK *and* GEMM kernels compile
        # (a 2x2 grid has no GEMM task and would leak its XLA compile
        # into the first timed rep)
        wm = make_spd(3 * nb, dtype=dtype)
        Aw = TwoDimBlockCyclic(3 * nb, 3 * nb, nb, nb, dtype=dtype).from_numpy(wm)
        ctx.add_taskpool(dpotrf_taskpool(Aw))
        ctx.wait()

        tpu_devs = [d for d in ctx.devices if d.device_type == "tpu"]
        best = None
        A = None
        for _ in range(reps):
            A = TwoDimBlockCyclic(n, n, nb, nb, dtype=dtype).from_numpy(M)
            # prestage tiles into HBM (steady-state model: data lives on
            # device; the timed region measures the factorization DAG)
            if tpu_devs:
                import jax
                for (tm, tn) in A.tiles():
                    tpu_devs[0].data_advise(A.data_of(tm, tn), "prefetch")
                jax.block_until_ready([
                    A.data_of(tm, tn).get_copy(tpu_devs[0].device_index).payload
                    for (tm, tn) in A.tiles()])
            t0 = time.perf_counter()
            tp = dpotrf_taskpool(A)
            ctx.add_taskpool(tp)
            ctx.wait()
            # the DAG is done when every output tile's device result
            # exists; block on the newest copies so async dispatch is
            # fully timed
            import jax
            pend = []
            for (tm, tn) in A.tiles():
                c = A.data_of(tm, tn).newest_copy()
                if c is not None and c.payload is not None:
                    pend.append(c.payload)
            jax.block_until_ready(pend)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        nt = (n + nb - 1) // nb
        n_tasks = nt * (nt + 1) * (nt + 2) // 6
        _MODE_NOTES["runtime_classic"] = {
            "classic_wall_us_per_task": round(best * 1e6 / n_tasks, 1)}
        return best, check_numerics(A.to_numpy(), M, n)
    finally:
        ctx.fini()


def bench_chip_gemm(n=4096, chain=24, reps=3):
    """Bare-chip GEMM microbench: what one jitted matmul sustains with
    no runtime around it (an observation beside the engine rates, not a
    peak: the published peaks per device_kind are the benchmark's to
    table, ROADMAP S1).  Two readings: one GEMM timed to completion
    with a tiny call's round-trip subtracted, and K dependent GEMMs
    behind one sync.  Returns the details dict."""
    import jax
    rng = np.random.RandomState(0)
    x = jax.device_put(rng.rand(n, n).astype(np.float32))
    s = jax.device_put(rng.rand(128, 128).astype(np.float32))
    f = jax.jit(lambda a: a @ a * (1.0 / a.shape[0]))
    jax.block_until_ready(f(x))
    jax.block_until_ready(f(s))

    def best_of(fn, k=reps):
        b = None
        for _ in range(k):
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            b = dt if b is None or dt < b else b
        return b

    t_small = best_of(lambda: jax.block_until_ready(f(s)))
    t_sync = best_of(lambda: jax.block_until_ready(f(x)))

    def chain_run():
        y = f(x)
        for _ in range(chain - 1):
            y = f(y)
        jax.block_until_ready(y)

    t_chain = best_of(chain_run) / chain
    flops = 2.0 * n ** 3
    return {"sync_ms": round(t_sync * 1e3, 3),
            "call_latency_ms": round(t_small * 1e3, 3),
            "chained_gflops": round(flops / t_chain / 1e9, 1),
            "sync_amortized_gflops": round(
                flops / max(t_sync - t_small, 1e-9) / 1e9, 1)}


def bench_link(size_mb=4, reps=2):
    """H2D/D2H bandwidth of the host link, as extras on the record."""
    import jax
    x = np.random.RandomState(1).rand(size_mb * (1 << 18)).astype(np.float32)
    best_h = best_d = None
    for _ in range(reps):
        t0 = time.perf_counter()
        xd = jax.device_put(x)
        jax.block_until_ready(xd)
        th = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(xd)
        td = time.perf_counter() - t0
        best_h = th if best_h is None else min(best_h, th)
        best_d = td if best_d is None else min(best_d, td)
    return {"link_h2d_mbps": round(size_mb / best_h, 1),
            "link_d2h_mbps": round(size_mb / best_d, 1)}


def bench_all(n, nb, reps, cores, dtype):
    """The composite: run every engineering mode {capture_chain,
    wave@1024/512, capture, runtime@512} plus the bare-chip GEMM
    microbench, carry them ALL in extras, and emit the headline from
    the BEST numerics-passing mode.  Nothing is retried and nothing is
    caught: a leg that raises ends the run with its traceback and a
    non-zero exit, and so does a run in which no mode passes numerics.
    """
    extras = {}
    candidates = []   # (mode_label, n_used, nb_used, gflops)

    def _record(mode, n_used, nb_used, r):
        best, err = r
        key = f"{mode}_gflops(N={n_used},NB={nb_used})"
        if err < NUMERICS_TOL:
            gf = dpotrf_flops(n_used) / best / 1e9
            extras[key] = round(gf, 2)
            candidates.append((mode, n_used, nb_used, gf))
        else:
            extras[key] = f"numerics failed: {err}"

    det = bench_chip_gemm()
    extras["chip_gemm_detail"] = det
    extras["call_latency_ms"] = det["call_latency_ms"]
    extras.update(bench_link())

    # K factorizations of the captured DAG behind ONE XLA call with
    # on-device synthesis + residual: total wall time is 1x call
    # latency + K x compute.  NB=2048: the capture compile scales with
    # the task count (NB=1024 at N=32768 is 5,984 tasks)
    chain_nb = int(os.environ.get("BENCH_CHAIN_NB", "2048"))
    chain_k = int(os.environ.get("BENCH_CHAIN_K", "4"))
    chain_n = int(os.environ.get("BENCH_CHAIN_N", "32768"))
    extras["capture_chain_k"] = chain_k
    _record("capture_chain", chain_n, chain_nb,
            bench_capture_chain(chain_n, chain_nb, reps, dtype, chain_k))

    _record("wave", n, 1024, bench_wave(n, 1024, reps, dtype))
    _record("wave", n, 512, bench_wave(n, 512, reps, dtype))
    _record("capture", n, nb, bench_capture(n, nb, reps, dtype))
    n_rt = int(os.environ.get("BENCH_RUNTIME_N", "4096"))
    _record("runtime", n_rt, 512,
            bench_runtime(n_rt, 512, max(2, reps), cores, dtype))
    # the historical dynamic-hash + scheduler path, for continuity
    _record("runtime_classic", n_rt, 512,
            bench_runtime(n_rt, 512, max(2, reps), cores, dtype,
                          dispatch="classic"))

    for note in _MODE_NOTES.values():
        extras.update(note)
    if "turbo_dispatch_us_per_task" in extras and \
            "classic_wall_us_per_task" in extras:
        # submission vs wall: the CPU-side dispatch rate is the
        # framework's own number beside the wall ratio above
        extras["turbo_submit_vs_classic_wall"] = round(
            extras["classic_wall_us_per_task"]
            / max(extras["turbo_dispatch_us_per_task"], 1e-9), 2)
    extras.update(bench_engine_cpu())
    # the CPU-subprocess legs below are host measurements that ride
    # every record (each has its own BENCH_<LEG>=0 switch)
    extras.update(bench_comm(n_msgs=2000, bulk_mb=8, reps=2))
    if os.environ.get("BENCH_MESH", "1") != "0":
        extras.update(bench_mesh(reps=2))
    if os.environ.get("BENCH_OVERLAP", "1") != "0":
        extras.update(bench_overlap())
    if os.environ.get("BENCH_QWIRE", "1") != "0":
        extras.update(bench_qwire())
    if os.environ.get("BENCH_TRACE", "1") != "0":
        extras.update(bench_trace())
    if os.environ.get("BENCH_HEALTH", "1") != "0":
        extras.update(bench_health())
    if os.environ.get("BENCH_DPLANE", "1") != "0":
        extras.update(bench_dplane())
    if os.environ.get("BENCH_SERVE", "1") != "0":
        extras.update(bench_serve())
    if os.environ.get("BENCH_AUTOTUNE", "1") != "0":
        extras.update(bench_autotune())
    if os.environ.get("BENCH_STAGEC", "1") != "0":
        extras.update(bench_stagec(reps=2))
    # the second workload (dgeqrf) so it stops rotting silently
    if os.environ.get("BENCH_GEQRF", "1") != "0":
        geqrf_n = int(os.environ.get("BENCH_GEQRF_N", "1024"))
        best_g, err_g, gex = bench_geqrf(
            n=geqrf_n,
            nb=int(os.environ.get("BENCH_GEQRF_NB", "128")),
            reps=2, cores=cores, dtype=dtype)
        extras.update(gex)
        if err_g < NUMERICS_TOL:
            extras["geqrf_gflops"] = round(
                dgeqrf_flops(geqrf_n) / best_g / 1e9, 2)
    if not candidates:
        emit_json({"metric": "dpotrf_gflops",
                   "metric_id": "dpotrf_gflops/none", "mode": "all",
                   "value": 0.0, "unit": "GFLOP/s", "vs_baseline": 0.0,
                   "error": "no mode passed numerics",
                   "extras": extras})
        raise SystemExit(1)
    mode, n_used, nb_used, gf = max(candidates, key=lambda c: c[3])
    emit_line(n_used, nb_used, dtype, mode, gf, extras)


_ENGINE_CPU_DRIVER = r"""
import json, os, sys
sys.path.insert(0, os.environ["BENCH_REPO"])
import numpy as np
import bench

# dispatch-BOUND sizing (tiny kernels): the point is the per-task
# engine cost, the regime the reference's ~1 us/task is quoted in
# (scheduling.c:586-625); larger nb re-mixes kernel time into both
# numbers and compresses the ratio toward 1. Both paths reuse
# bench_runtime — ONE measurement methodology, no driver drift.
n, nb, reps = 512, 32, 3
turbo_s, terr = bench.bench_runtime(n, nb, reps, 1, np.dtype(np.float32))
classic_s, cerr = bench.bench_runtime(n, nb, reps, 1, np.dtype(np.float32),
                                      dispatch="classic")
nt = (n + nb - 1) // nb
print(json.dumps({"turbo_s": float(turbo_s), "classic_s": float(classic_s),
                  "n_tasks": nt * (nt + 1) * (nt + 2) // 6,
                  "turbo_err": float(terr), "classic_err": float(cerr)}))
"""


def _scrubbed_bench_env(n_devices=None, **extra) -> dict:
    """Whitelist-constructed env for a scrubbed CPU bench subprocess:
    only the XLA host platform exists, whatever jax state the calling
    process carries (a parent that has touched JAX holds the chip, so
    a child must never reach for it). ONE copy — every subprocess
    bench rides it, so a scrub-policy change lands everywhere at once.
    ``n_devices`` sets the virtual CPU mesh size; ``extra`` entries
    (stringified) ride on top.  The compile-cache placement is part of
    the deployment and is carried."""
    repo = os.path.dirname(os.path.abspath(__file__))
    keep = ("PATH", "HOME", "LANG", "LC_ALL", "TMPDIR", "USER",
            "JAX_COMPILATION_CACHE_DIR")
    env = {k: os.environ[k] for k in keep if k in os.environ}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo, BENCH_REPO=repo,
               PARSEC_MCA_device_tpu_platform="cpu")
    if n_devices is not None:
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{n_devices}")
    env.update({k: str(v) for k, v in extra.items()})
    return env


def bench_engine_cpu() -> dict:
    """Host-side engine comparison: turbo vs classic per-task dispatch
    on the XLA host (CPU) backend in a scrubbed subprocess — the same
    dispatch code paths as the chip with the device taken out, so the
    ratio is host dispatch cost only (a host measurement, not a chip
    speed). BENCH_ENGINE_CPU=0 skips it (~1 min of subprocess jax
    imports + CPU kernel compiles)."""
    import subprocess
    import sys as _sys

    if os.environ.get("BENCH_ENGINE_CPU", "1") == "0":
        return {}
    env = _scrubbed_bench_env()
    try:
        p = subprocess.run([_sys.executable, "-c", _ENGINE_CPU_DRIVER],
                           env=env, capture_output=True, text=True,
                           timeout=600)
        if p.returncode != 0:
            return {"engine_cpu_error": p.stdout[-200:] + p.stderr[-200:]}
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        us = 1e6 / max(rec["n_tasks"], 1)
        return {
            "turbo_cpu_us_per_task": round(rec["turbo_s"] * us, 1),
            "classic_cpu_us_per_task": round(rec["classic_s"] * us, 1),
            "turbo_vs_classic_cpu": round(
                rec["classic_s"] / max(rec["turbo_s"], 1e-9), 2),
        }
    except Exception as exc:  # noqa: BLE001
        return {"engine_cpu_error": repr(exc)[:200]}


# ---------------------------------------------------------------------- #
# comm-engine wire microbenchmark (ISSUE 2): msgs/s and MB/s over the    #
# LocalFabric and loopback TCP, small-AM rate with/without coalescing    #
# ---------------------------------------------------------------------- #
def _tcp_pair(**knobs):
    """Two loopback TCP engines brought up concurrently."""
    import concurrent.futures as cf
    from parsec_tpu.comm.tcp import TCPCommEngine, free_ports

    ports = free_ports(2)
    eps = [("127.0.0.1", p) for p in ports]
    with cf.ThreadPoolExecutor(2) as ex:
        return list(ex.map(lambda r: TCPCommEngine(r, eps, **knobs),
                           range(2)))


def bench_comm_small_am(n_msgs=4000, coalesce=True, reps=2):
    """Small-AM throughput over loopback TCP: ``n_msgs`` tiny dict
    payloads burst from rank 0, rank 1 spins progress until all land.
    ``coalesce=False`` forces one frame+syscall per message (the
    per-message path the coalesced fast path is measured against).
    Returns best msgs/s."""
    e0, e1 = _tcp_pair(
        coalesce_max_bytes=(1 << 16) if coalesce else 0)
    try:
        got = []
        e1.tag_register(100, lambda src, p: got.append(p))
        best = None
        for _ in range(reps):
            got.clear()
            t0 = time.perf_counter()
            for i in range(n_msgs):
                e0.send_am(1, 100, {"i": i})
            deadline = time.time() + 60
            while len(got) < n_msgs and time.time() < deadline:
                if not e1.progress():
                    # idle poll: yield the GIL like a parked worker
                    # would — a busy spin starves the socket threads
                    # for the full thread switch interval
                    time.sleep(0.0002)
            dt = time.perf_counter() - t0
            if len(got) != n_msgs:
                raise RuntimeError(
                    f"only {len(got)}/{n_msgs} messages arrived")
            best = dt if best is None else min(best, dt)
        return n_msgs / best
    finally:
        e0.fini()
        e1.fini()


def bench_comm(n_msgs=4000, bulk_mb=8, reps=2):
    """The comm wire microbenchmark: small-AM msgs/s over the
    LocalFabric and loopback TCP (coalesced vs per-message), bulk MB/s
    over the chunked path, and a small control AM's delivery latency
    while a multi-MB payload is in flight. Returns a flat extras dict
    (also the BENCH_MODE=comm payload)."""
    from parsec_tpu.comm.local import LocalFabric

    out = {}
    # LocalFabric ceiling: the in-process queue, no wire at all
    fab = LocalFabric(2)
    l0, l1 = fab.engine(0), fab.engine(1)
    got = []
    l1.tag_register(100, lambda src, p: got.append(p))
    t0 = time.perf_counter()
    for i in range(n_msgs):
        l0.send_am(1, 100, {"i": i})
    deadline = time.time() + 60
    while len(got) < n_msgs and time.time() < deadline:
        l1.progress()
    if len(got) != n_msgs:
        raise RuntimeError(f"only {len(got)}/{n_msgs} local msgs arrived")
    out["comm_local_small_msgs_per_s"] = round(
        n_msgs / (time.perf_counter() - t0))

    coalesced = bench_comm_small_am(n_msgs, coalesce=True, reps=reps)
    percall = bench_comm_small_am(n_msgs, coalesce=False, reps=reps)
    out["comm_tcp_small_msgs_per_s"] = round(coalesced)
    out["comm_tcp_small_msgs_per_s_percall"] = round(percall)
    out["comm_coalesce_speedup"] = round(coalesced / percall, 2)

    # bulk MB/s through the chunked pipeline + control-AM latency while
    # a multi-MB payload is in flight (the head-of-line-blocking probe)
    e0, e1 = _tcp_pair()
    try:
        arrivals = []
        e1.tag_register(101, lambda src, p: arrivals.append(("bulk", p)))
        e1.tag_register(102, lambda src, p: arrivals.append(
            ("ctrl", time.perf_counter())))
        big = np.random.RandomState(0).rand(
            bulk_mb * (1 << 17)).astype(np.float64)  # bulk_mb MB
        best = None
        best_lat = None
        overtook = False
        for _ in range(reps):
            arrivals.clear()
            t0 = time.perf_counter()
            e0.send_am(1, 101, {"arr": big})
            t_ctrl = time.perf_counter()
            e0.send_am(1, 102, {"go": 1})
            deadline = time.time() + 120
            while len(arrivals) < 2 and time.time() < deadline:
                if not e1.progress():
                    time.sleep(0.0002)
            dt = time.perf_counter() - t0
            if len(arrivals) != 2:
                raise RuntimeError("bulk/ctrl messages did not arrive")
            kinds = [k for k, _v in arrivals]
            ctrl_at = next(v for k, v in arrivals if k == "ctrl")
            # best-of-reps, like the bulk rate below: one noisy rep
            # must not misreport the HOL-blocking probe
            lat = (ctrl_at - t_ctrl) * 1e3
            best_lat = lat if best_lat is None else min(best_lat, lat)
            overtook = overtook or kinds[0] == "ctrl"
            best = dt if best is None else min(best, dt)
        out["comm_ctrl_latency_under_bulk_ms"] = round(best_lat, 3)
        out["comm_ctrl_overtook_bulk"] = overtook
        out["comm_tcp_bulk_mbps"] = round(bulk_mb / best, 1)
        out["comm_tcp_chunks_sent"] = e0.wire_stats["chunks_sent"]
        out["comm_tcp_coalesced_msgs"] = e0.wire_stats["coalesced_msgs"]
    finally:
        e0.fini()
        e1.fini()
    return out


# ---------------------------------------------------------------------- #
# reliable-session microbenchmark (ISSUE 10): reconnect latency after a   #
# link flap, replay volume, and the seq/ack envelope's throughput cost    #
# ---------------------------------------------------------------------- #
def bench_linkchaos(reps=3, n_msgs=2000):
    """BENCH_MODE=linkchaos: the reliable-session layer measured three
    ways over loopback TCP — (a) small-AM throughput with sessions ON
    vs OFF (the K_SEQ envelope + replay-window retention overhead on
    the fault-free fast path), (b) flap-to-recovered latency: the wall
    from a hard link tear to the first post-fault delivery (reconnect
    handshake + replay included), and (c) the replay/dedup volume the
    faults actually exercised."""
    import socket as _socket

    def msgs_per_s(**knobs):
        e0, e1 = _tcp_pair(**knobs)
        try:
            got = []
            e1.tag_register(100, lambda src, p: got.append(p))
            best = None
            for _ in range(reps):
                got.clear()
                t0 = time.perf_counter()
                for i in range(n_msgs):
                    e0.send_am(1, 100, {"i": i})
                deadline = time.time() + 60
                while len(got) < n_msgs and time.time() < deadline:
                    if not e1.progress():
                        time.sleep(0.0002)
                dt = time.perf_counter() - t0
                if len(got) != n_msgs:
                    raise RuntimeError(
                        f"only {len(got)}/{n_msgs} messages arrived")
                best = dt if best is None else min(best, dt)
            return n_msgs / best
        finally:
            e0.fini()
            e1.fini()

    out = {}
    base = msgs_per_s()
    sess = msgs_per_s(reconnect_timeout=10.0)
    out["linkchaos_msgs_per_s_session_off"] = round(base)
    out["linkchaos_msgs_per_s_session_on"] = round(sess)
    out["linkchaos_session_overhead_pct"] = round((base / sess - 1) * 100, 1)

    # flap-to-recovered latency: tear the established socket, then time
    # until a fresh message crosses the resumed session (reconnect
    # handshake + gap replay are both inside the measured wall)
    e0, e1 = _tcp_pair(reconnect_timeout=10.0, reconnect_backoff=0.02)
    try:
        got = []
        e1.tag_register(100, lambda src, p: got.append(p["i"]))
        deadline = time.time() + 10
        while time.time() < deadline:
            with e0._conn_cond:
                p01 = e0._peers.get(1)
            if p01 is not None and p01.rs_ok:
                break
            time.sleep(0.005)
        lats = []
        seq = 0
        for _ in range(reps):
            # a burst in flight when the link tears -> real replay work
            for _ in range(50):
                e0.send_am(1, 100, {"i": seq})
                seq += 1
            t0 = time.perf_counter()
            p01.sock.shutdown(_socket.SHUT_RDWR)
            e0.send_am(1, 100, {"i": seq})
            seq += 1
            deadline = time.time() + 30
            while len(got) < seq and time.time() < deadline:
                if not e1.progress():
                    time.sleep(0.0002)
            if len(got) != seq:
                raise RuntimeError(
                    f"only {len(got)}/{seq} messages after the flap")
            lats.append((time.perf_counter() - t0) * 1e3)
        assert got == list(range(seq)), "delivery not exactly-once/ordered"
        out["linkchaos_reconnect_ms"] = round(min(lats), 2)
        out["linkchaos_reconnect_ms_max"] = round(max(lats), 2)
        out["linkchaos_reconnects"] = e0.wire_stats["reconnects"]
        out["linkchaos_replayed_frames"] = e0.wire_stats["replayed_frames"]
        out["linkchaos_dup_dropped"] = e1.wire_stats["dup_dropped"]
    finally:
        e0.fini()
        e1.fini()
    return out


# ---------------------------------------------------------------------- #
# fault-tolerance microbenchmark (ISSUE 4): heartbeat detection latency   #
# over loopback TCP + snapshot/rollback overhead of the restart driver    #
# ---------------------------------------------------------------------- #
def bench_ft(reps=3, interval=0.01, timeout=0.15):
    """Two probes. (1) Detection latency: loopback TCP pair with the
    proactive detector on rank 0; rank 1 is chaos-silenced (sockets
    stay open — only heartbeats can find it) and we time
    silence -> eviction, best of ``reps``. (2) Restart overhead: a
    small single-rank dpotrf through ft.restart.run_with_restart with
    snapshot-every-stage vs the bare run, plus recovery wall time for
    an injected transient task fault."""
    import tempfile

    from parsec_tpu.ft import HeartbeatDetector, run_with_restart, RestartPolicy

    out = {}
    best = None
    rtt_ms = 0.0
    for _ in range(reps):
        e0, e1 = _tcp_pair()
        det = HeartbeatDetector(e0, interval, timeout)
        try:
            det.start()
            deadline = time.time() + 10
            while not det.is_established(1) and time.time() < deadline:
                time.sleep(0.002)
            if not det.is_established(1):
                raise RuntimeError("heartbeat never established")
            rtt_ms = max(rtt_ms, (det.rtt_s(1) or 0.0) * 1e3)
            e1.ft_silence()
            t0 = time.perf_counter()
            while 1 not in e0.dead_peers and time.time() < deadline:
                time.sleep(0.001)
            if 1 not in e0.dead_peers:
                raise RuntimeError("silenced peer never detected")
            lat = time.perf_counter() - t0
            best = lat if best is None else min(best, lat)
        finally:
            det.stop()
            e0.fini()
            e1.fini()
    out["ft_detection_latency_ms"] = round(best * 1e3, 2)
    out["ft_heartbeat_timeout_ms"] = round(timeout * 1e3, 2)
    out["ft_hb_rtt_ms"] = round(rtt_ms, 3)

    # restart overhead: bare dpotrf vs snapshot-every-stage driver
    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.ops import dpotrf_taskpool, make_spd
    from parsec_tpu.utils.params import params as _params

    n, nb = 256, 64
    M = make_spd(n)

    def run(driver):
        ctx = parsec_tpu.init(nb_cores=2, enable_tpu=False)
        try:
            A = TwoDimBlockCyclic(n, n, nb, nb,
                                  dtype=np.float32).from_numpy(M)
            t0 = time.perf_counter()
            driver(ctx, A)
            return time.perf_counter() - t0
        finally:
            ctx.fini()

    def bare(ctx, A):
        ctx.add_taskpool(dpotrf_taskpool(A))
        ctx.wait()

    run(bare)   # warmup: first-use costs must not skew the comparison
    t_bare = min(run(bare) for _ in range(reps))
    with tempfile.TemporaryDirectory() as d:
        def snap(ctx, A):
            run_with_restart(
                ctx, [lambda: dpotrf_taskpool(A)], [A],
                os.path.join(d, "bench"),
                policy=RestartPolicy("restart", retries=1, every=1))

        t_snap = min(run(snap) for _ in range(reps))

        # recovery wall time: one injected transient fault, one retry
        _params.set_cmdline("ft_inject", "taskfail:nth=2")
        try:
            t_recover = run(lambda ctx, A: run_with_restart(
                ctx, [lambda: dpotrf_taskpool(A)], [A],
                os.path.join(d, "bench_r"),
                policy=RestartPolicy("restart", retries=2, backoff=0.01)))
        finally:
            _params.reset()
    out["ft_dpotrf_bare_s"] = round(t_bare, 4)
    out["ft_dpotrf_snapshot_s"] = round(t_snap, 4)
    out["ft_snapshot_overhead_pct"] = round(
        (t_snap / t_bare - 1.0) * 100.0, 1)
    out["ft_recover_after_taskfail_s"] = round(t_recover, 4)
    return out


def bench_elastic(reps=3, n=512, nb=64):
    """Elastic grid recovery (ISSUE 9). Two probes.

    (1) Reshard throughput: a 4-writer snapshot reshard-restored onto a
    2-rank in-process grid through ``collections/redistribute`` — the
    cross-grid restore wall and MB/s, best of ``reps``.
    (2) Shrink recovery: the ex13 scenario inline — 3-rank checkpointed
    dpotrf, rank 2 chaos-killed, ``ft_elastic=shrink`` — total wall vs
    the failure-free run on the same grid; the delta is detection +
    agreement + reshard + replay, the price of losing a rank with no
    operator in the loop."""
    import tempfile

    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.utils import checkpoint as ckpt
    from parsec_tpu.utils.params import params as _params
    from parsec_tpu.utils.spmd import spmd_threads

    out = {}
    M = np.arange(n * n, dtype=np.float32).reshape(n, n) / n

    def dist(rank, nodes, P, Q):
        d = TwoDimBlockCyclic(n, n, nb, nb, P=P, Q=Q, nodes=nodes,
                              rank=rank, dtype=np.float32)
        d.name = "descA"
        for (i, j) in d.local_tiles():
            np.copyto(d.tile(i, j),
                      M[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb])
        return d

    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "snap.c0")
        res, _ = spmd_threads(
            4, lambda r, f: bool(ckpt.save_collection(dist(r, 4, 4, 1),
                                                      prefix)))
        assert all(res)

        def restore_rank(rank, fabric):
            eng = RemoteDepEngine(fabric.engine(rank))
            ctx = parsec_tpu.Context(nb_cores=1, comm=eng,
                                     enable_tpu=False)
            try:
                d = TwoDimBlockCyclic(n, n, nb, nb, P=2, Q=1, nodes=2,
                                      rank=rank, dtype=np.float32)
                d.name = "descA"
                t0 = time.perf_counter()
                ckpt.restore_collection(d, prefix, reshard=True,
                                        context=ctx)
                return time.perf_counter() - t0
            finally:
                ctx.fini()

        best = None
        for _ in range(reps):
            res, _ = spmd_threads(2, restore_rank)
            wall = max(res)
            best = wall if best is None else min(best, wall)
        out["elastic_reshard_wall_ms"] = round(best * 1e3, 2)
        out["elastic_reshard_mb_s"] = round(
            n * n * 4 / best / 1e6, 1)

    # shrink recovery: the ex13 scenario inline, chaos vs failure-free
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "examples"))
    import ex13_elastic_shrink as ex13

    def scenario(inject):
        with tempfile.TemporaryDirectory() as td:
            t0 = time.perf_counter()
            results, _ = spmd_threads(
                ex13.NB_RANKS,
                lambda r, f: ex13.run_rank(
                    r, f, ex13.make_spd(ex13.N), os.path.join(td, "ck")),
                timeout=600)
            wall = time.perf_counter() - t0
        ok = [r for r, o in enumerate(results) if o[0] == "ok"]
        es = results[ok[0]][3]
        return wall, ok, results[ok[0]][2], es

    _params.set_cmdline("ft_heartbeat_interval", "0.05")
    _params.set_cmdline("ft_heartbeat_timeout", "3.0")
    _params.set_cmdline("ft_elastic", "shrink")
    try:
        _params.set_cmdline("ft_inject", "")
        t_clean, ok, _, _ = scenario(False)
        assert ok == [0, 1, 2], ok
        _params.set_cmdline("ft_inject", "kill:rank=2:after=4")
        t_chaos, ok, stats, es = scenario(True)
        assert ok == [0, 1] and stats["grid"] == (0, 1), (ok, stats)
        assert es["elastic_resizes"] == 1 and es["reshard_bytes"] > 0, es
    finally:
        _params.reset()
    out["elastic_dpotrf_clean_s"] = round(t_clean, 3)
    out["elastic_dpotrf_shrink_s"] = round(t_chaos, 3)
    out["elastic_shrink_recovery_s"] = round(t_chaos - t_clean, 3)
    out["elastic_reshard_bytes"] = es["reshard_bytes"]
    return out


def bench_mesh_inner(burst=64, nb=96, reps=3, shape="2x2") -> dict:
    """Sharded vs single-chip batched dispatch (ISSUE 6): the same
    same-class DTD burst through the classic runtime's device module,
    once on ONE chip (``device_tpu_max=1``, the PR-5 batched path) and
    once on a ``device_mesh_shape`` chip mesh where each flush group
    compiles through shard_map and executes spread across the chips.
    Requires a multi-device jax host — ``bench_mesh`` wraps this in the
    scrubbed 8-virtual-device CPU subprocess."""
    import jax
    import jax.numpy as jnp
    import parsec_tpu
    from parsec_tpu import dtd
    from parsec_tpu.dsl.dtd import INOUT, INPUT
    from parsec_tpu.utils.params import params as _params

    kern = jax.jit(lambda c, a, b:
                   c - jnp.dot(a, b.T, preferred_element_type=jnp.float32))

    def run(mesh_shape):
        from contextlib import ExitStack
        with ExitStack() as stack:
            if mesh_shape:
                stack.enter_context(_params.cmdline_override(
                    "device_mesh_shape", mesh_shape))
            else:
                stack.enter_context(_params.cmdline_override(
                    "device_tpu_max", "1"))
            ctx = parsec_tpu.init(nb_cores=2)
            try:
                devs = [d for d in ctx.devices if d.device_type == "tpu"]
                if not devs:
                    return None
                if mesh_shape and not getattr(devs[0], "chips", None):
                    return None   # mesh fell back: report honestly
                best = None
                results = None
                for rep in range(reps):
                    rng = np.random.RandomState(0)   # same data each leg
                    tp = dtd.taskpool_new()
                    ctx.add_taskpool(tp)

                    def body(es, task):   # host fallback
                        c, a, b = dtd.unpack_args(task)
                        c -= a @ b.T

                    boot = tp.tile_of_array(
                        np.zeros((nb, nb), np.float32))
                    tp.insert_task(body, (boot, INOUT),
                                   (boot, INPUT), (boot, INPUT))
                    tp.add_chore(body, "tpu", kern)
                    tiles = [[tp.tile_of_array(
                        rng.rand(nb, nb).astype(np.float32))
                        for _ in range(3)] for _ in range(burst)]
                    s0 = {k: sum(d.stats[k] for d in devs)
                          for k in devs[0].stats}
                    t0 = time.perf_counter()
                    for c, a, b in tiles:
                        tp.insert_task(body, (c, INOUT),
                                       (a, INPUT), (b, INPUT))
                    tp.wait()
                    dt = time.perf_counter() - t0
                    st = {k: sum(d.stats[k] for d in devs) - s0[k]
                          for k in devs[0].stats}
                    r = {"wall_us_per_task": round(dt / burst * 1e6, 1),
                         "dispatch_us_per_task": round(
                             st["dispatch_ns"] / 1e3
                             / max(1, st["dispatch_tasks"]), 2),
                         "batches": st["batches"],
                         "mesh_dispatches": st.get("mesh_dispatches", 0),
                         "mesh_tasks": st.get("mesh_tasks", 0),
                         "collective_bytes": st.get("collective_bytes", 0)}
                    if best is None or (r["wall_us_per_task"]
                                        < best["wall_us_per_task"]):
                        best = r
                        results = [np.asarray(
                            c.data.sync_to_host().payload)
                            for c, _a, _b in tiles]
                return best, results
            finally:
                ctx.fini()

    out = {"mesh_burst": burst, "mesh_nb": nb, "mesh_shape": shape}
    run(None)          # warmup: compile cost must not skew either leg
    single = run(None)
    mesh = run(shape)
    if single is None or mesh is None:
        out["error"] = ("no XLA device attached" if single is None
                        else "mesh unavailable (chips/shard_map)")
        return out
    (single, res_s), (mesh, res_m) = single, mesh
    out.update({f"single_{k}": v for k, v in single.items()
                if not k.startswith("mesh")})
    out.update({f"mesh_{k}": v for k, v in mesh.items()})
    out["mesh_bit_exact_vs_single"] = bool(
        all((a == b).all() for a, b in zip(res_s, res_m)))
    out["mesh_vs_single_wall"] = round(
        single["wall_us_per_task"]
        / max(1e-9, mesh["wall_us_per_task"]), 2)
    out["mesh_vs_single_dispatch"] = round(
        single["dispatch_us_per_task"]
        / max(1e-9, mesh["dispatch_us_per_task"]), 2)
    return out


_MESH_DRIVER = r"""
import json, os, sys
sys.path.insert(0, os.environ["BENCH_REPO"])
import bench

print(json.dumps(bench.bench_mesh_inner(
    burst=int(os.environ.get("BENCH_MESH_BURST", "64")),
    nb=int(os.environ.get("BENCH_MESH_NB", "96")),
    reps=int(os.environ.get("BENCH_REPS", "3")),
    shape=os.environ.get("BENCH_MESH_SHAPE", "2x2"))))
"""


def bench_mesh(burst=64, nb=96, reps=3, shape="2x2") -> dict:
    """BENCH_MODE=mesh: mesh-sharded vs single-chip batched dispatch in
    a scrubbed multi-device CPU subprocess (the 8-virtual-device host
    is where a mesh exists on a one-chip machine — same pattern as
    bench_engine_cpu)."""
    import subprocess
    import sys as _sys

    gp, gq = (int(x) for x in (shape.split("x") if "x" in shape
                               else ("1", shape)))
    env = _scrubbed_bench_env(
        n_devices=max(8, gp * gq),
        BENCH_MESH_BURST=burst, BENCH_MESH_NB=nb,
        BENCH_REPS=reps, BENCH_MESH_SHAPE=shape)
    try:
        p = subprocess.run([_sys.executable, "-c", _MESH_DRIVER],
                           env=env, capture_output=True, text=True,
                           timeout=900)
        if p.returncode != 0:
            return {"mesh_error": p.stdout[-200:] + p.stderr[-200:]}
        return json.loads(p.stdout.strip().splitlines()[-1])
    except Exception as exc:  # noqa: BLE001
        return {"mesh_error": repr(exc)[:200]}


def bench_dispatch(burst=64, nb=96, reps=3) -> dict:
    """BENCH_MODE=dispatch: batched vs per-task device dispatch.

    A same-class burst of ``burst`` independent (nb, nb) GEMM-ish DTD
    tasks through the classic runtime's device module, once with
    ``device_batch_max=1`` (one XLA submission per task — the
    pre-batching behavior) and once with the batched-dispatch +
    prefetch pipeline on.  The headline is the amortized CPU-side
    dispatch cost per task (``PARSEC::DEVICE::*::DISPATCH_US`` — the
    submit cost batching amortizes); wall µs/task, batch occupancy and
    prefetch hit rate ride along in extras.
    """
    import jax
    import jax.numpy as jnp
    import parsec_tpu
    from parsec_tpu import dtd
    from parsec_tpu.dsl.dtd import INOUT, INPUT
    from parsec_tpu.utils.params import params as _params

    kern = jax.jit(lambda c, a, b:
                   c - jnp.dot(a, b.T, preferred_element_type=jnp.float32))

    def run(batch_max, prefetch):
        with _params.cmdline_override("device_batch_max", str(batch_max)), \
             _params.cmdline_override("device_prefetch_depth", str(prefetch)), \
             _params.cmdline_override("device_tpu_max", "1"):
            ctx = parsec_tpu.init(nb_cores=2)
            try:
                devs = [d for d in ctx.devices if d.device_type == "tpu"]
                if not devs:
                    return None
                def snap():
                    return {k: sum(d.stats[k] for d in devs)
                            for k in devs[0].stats}

                best = None   # the steady-state rep: min dispatch us/task
                for rep in range(reps):
                    rng = np.random.RandomState(rep)
                    tp = dtd.taskpool_new()
                    ctx.add_taskpool(tp)

                    def body(es, task):   # host fallback
                        c, a, b = dtd.unpack_args(task)
                        c -= a @ b.T

                    boot = tp.tile_of_array(
                        np.zeros((nb, nb), np.float32))
                    tp.insert_task(body, (boot, INOUT),
                                   (boot, INPUT), (boot, INPUT))
                    tp.add_chore(body, "tpu", kern)
                    tiles = [[tp.tile_of_array(
                        rng.rand(nb, nb).astype(np.float32))
                        for _ in range(3)] for _ in range(burst)]
                    s0 = snap()
                    t0 = time.perf_counter()
                    for c, a, b in tiles:
                        tp.insert_task(body, (c, INOUT),
                                       (a, INPUT), (b, INPUT))
                    tp.wait()
                    dt = time.perf_counter() - t0
                    st = {k: v - s0[k] for k, v in snap().items()}
                    disp_us = (st["dispatch_ns"] / 1e3
                               / max(1, st["dispatch_tasks"]))
                    r = {"dispatch_us_per_task": round(disp_us, 2),
                         "wall_us_per_task": round(dt / burst * 1e6, 1),
                         "batches": st["batches"],
                         "batch_occupancy": round(
                             st["batched_tasks"] / st["batches"], 2)
                         if st["batches"] else 0.0,
                         "prefetch_issued": st["prefetch_issued"],
                         "prefetch_hit_rate": round(
                             st["prefetch_hits"]
                             / st["prefetch_issued"], 3)
                         if st["prefetch_issued"] else 0.0}
                    if best is None or (r["dispatch_us_per_task"]
                                        < best["dispatch_us_per_task"]):
                        best = r
                return best
            finally:
                ctx.fini()

    run(1, 0)          # warmup: jit/compile costs must not skew either leg
    per_task = run(1, 0)
    batched = run(int(os.environ.get("BENCH_DISPATCH_BATCH", "16")),
                  int(os.environ.get("BENCH_DISPATCH_PREFETCH", "4")))
    out = {"dispatch_burst": burst, "dispatch_nb": nb}
    if per_task is None or batched is None:
        out["error"] = "no XLA device attached"
        return out
    out.update({f"pertask_{k}": v for k, v in per_task.items()})
    out.update({f"batched_{k}": v for k, v in batched.items()})
    out["dispatch_speedup"] = round(
        per_task["dispatch_us_per_task"]
        / max(1e-9, batched["dispatch_us_per_task"]), 2)
    return out


def bench_overlap_inner(n=768, nb=64, ranks=2, delay_ms=8, cores=1,
                        reps=2) -> dict:
    """Overlap-aware execution on a THROTTLED link (ISSUE 7): the same
    classic-runtime dpotrf with the overlap pipeline ON (segmented
    flush + remote-GET prefetch + critical-path priorities, the
    defaults) vs OFF (whole-batch flush, no prefetch, static
    priorities — the pre-overlap behavior) — on a link where every
    frame pays an injected ``delay_ms`` sleep (ft/inject.py's delay op
    standing in for a slow inter-rank link).

    Each leg runs TWO stages: a plain dpotrf, then a second dpotrf
    whose registration rank 1 holds until rank 0's first activation
    races ahead of it — the real multi-pool pipeline window where the
    remote-GET prefetch engages (the payload fetch overlaps the hold
    instead of serializing behind counts_ready).  Each leg runs
    ``reps`` times; the reported overlap fraction POOLS the live
    tracker's interval totals (sum overlap_us / sum comm_us over all
    ranks and reps — one noisy rank/rep cannot flip the sign) and the
    wall is best-of-reps.  Also reports the segment/prefetch counters
    and whether the factors are bit-exact across legs (unroll
    segmentation must be)."""
    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.comm import LocalFabric, RemoteDepEngine
    from parsec_tpu.ops import dpotrf_taskpool, make_spd
    from parsec_tpu.utils.params import params as _params
    from parsec_tpu.utils.spmd import spmd_threads

    M = make_spd(n, dtype=np.float32)

    def run_once(on):
        from contextlib import ExitStack
        overrides = {
            "metrics": "1",
            "comm_mesh_local": "0",   # payloads must ride the (slow) wire
            "ft_inject": f"delay:pct=100:ms={delay_ms}",
            "device_flush_segments": "4" if on else "1",
            "comm_prefetch_inflight": "8" if on else "0",
            "sched_dynamic_priority": "1" if on else "0",
        }
        with ExitStack() as st:
            for k, v in overrides.items():
                st.enter_context(_params.cmdline_override(k, v))
            fabric = LocalFabric(ranks)

            def rank_fn(r, fab):
                eng = RemoteDepEngine(fab.engine(r))
                ctx = parsec_tpu.Context(nb_cores=cores, comm=eng)
                try:
                    t0 = time.perf_counter()
                    colls = []
                    for stage in range(2):
                        coll = TwoDimBlockCyclic(
                            n, n, nb, nb, dtype=np.float32,
                            P=ranks, Q=1, nodes=ranks, rank=r)
                        coll.name = f"descA{stage}"
                        coll.from_numpy(M.copy())
                        colls.append(coll)
                        tp = dpotrf_taskpool(coll, rank=r, nb_ranks=ranks)
                        if stage == 1 and r == 1:
                            # hold stage-2 registration until rank 0's
                            # activation races ahead of it (bounded):
                            # the GET-prefetch window of a multi-pool
                            # pipeline, identical in both legs — only
                            # whether the payload fetch overlaps the
                            # hold differs
                            deadline = time.time() + 10
                            while time.time() < deadline \
                                    and not eng._early_activations:
                                eng.ce.progress()
                                time.sleep(0.0005)
                        ctx.add_taskpool(tp)
                        ctx.wait()
                    wall = time.perf_counter() - t0
                    snap = ctx.obs.overlap.snapshot()
                    segs = sum(getattr(d, "stats", {}).get(
                        "flush_segments", 0) for d in ctx.devices)
                    comm_stats = dict(eng.stats)
                    owned = {(s, c): np.asarray(
                        coll.data_of(*c).sync_to_host().payload)
                        for s, coll in enumerate(colls)
                        for c in coll.tiles() if coll.rank_of(*c) == r}
                    return wall, snap, segs, comm_stats, owned
                finally:
                    ctx.fini()

            results, _fab = spmd_threads(ranks, rank_fn, timeout=900,
                                         fabric=fabric)
        tiles = {}
        for (_w, _snap, _s, _cs, owned) in results:
            tiles.update(owned)
        L = np.zeros((n, n), np.float32)
        for (s, (tm, tk)), t in tiles.items():
            if s == 0:
                L[tm * nb:tm * nb + t.shape[0],
                  tk * nb:tk * nb + t.shape[1]] = t
        Lt = np.tril(L).astype(np.float64)
        resid = float(np.abs(Lt @ Lt.T - M).max() / np.abs(M).max())
        return results, tiles, resid

    def leg(on):
        walls, comm_us, overlap_us = [], 0.0, 0.0
        segs = 0
        pf = {"prefetch_gets": 0, "prefetch_hits": 0,
              "prefetch_misses": 0, "prefetch_cancels": 0}
        tiles = resid = None
        for _ in range(reps):
            results, tiles, resid = run_once(on)
            walls.append(max(w for (w, _s, _g, _c, _t) in results))
            comm_us += sum(s["comm_us"] for (_w, s, _g, _c, _t) in results)
            overlap_us += sum(s["overlap_us"]
                              for (_w, s, _g, _c, _t) in results)
            segs += sum(g for (_w, _s, g, _c, _t) in results)
            for k in pf:
                pf[k] += sum(c[k] for (_w, _s, _g, c, _t) in results)
        out = {"wall_s": round(min(walls), 3),
               "overlap_fraction": round(overlap_us / max(1.0, comm_us),
                                         4),
               "flush_segments": segs, "residual": resid}
        out.update(pf)
        return out, tiles

    run_once(True)     # warmup: kernel/stacked-callable compiles
    on, tiles_on = leg(True)
    off, tiles_off = leg(False)
    out = {"overlap_n": n, "overlap_nb": nb, "overlap_ranks": ranks,
           "overlap_link_delay_ms": delay_ms, "overlap_reps": reps}
    out.update({f"on_{k}": v for k, v in on.items()})
    out.update({f"off_{k}": v for k, v in off.items()})
    out["overlap_bit_exact_on_vs_off"] = bool(
        set(tiles_on) == set(tiles_off)
        and all((tiles_on[c] == tiles_off[c]).all() for c in tiles_on))
    out["overlap_gain"] = round(
        on["overlap_fraction"] - off["overlap_fraction"], 4)
    out["overlap_wall_speedup"] = round(
        off["wall_s"] / max(1e-9, on["wall_s"]), 3)
    return out


_OVERLAP_DRIVER = r"""
import json, os, sys
sys.path.insert(0, os.environ["BENCH_REPO"])
import bench

print(json.dumps(bench.bench_overlap_inner(
    n=int(os.environ.get("BENCH_OVERLAP_N", "768")),
    nb=int(os.environ.get("BENCH_OVERLAP_NB", "64")),
    ranks=int(os.environ.get("BENCH_OVERLAP_RANKS", "2")),
    delay_ms=int(os.environ.get("BENCH_OVERLAP_DELAY_MS", "8")))))
"""


def bench_overlap(n=768, nb=64, ranks=2, delay_ms=8) -> dict:
    """BENCH_MODE=overlap: the throttled-link overlap on/off comparison
    in a scrubbed CPU subprocess (same pattern as bench_mesh: the
    numbers are host measurements and must not depend on the parent's
    chip)."""
    import subprocess
    import sys as _sys

    env = _scrubbed_bench_env(
        n_devices=2,
        BENCH_OVERLAP_N=n, BENCH_OVERLAP_NB=nb,
        BENCH_OVERLAP_RANKS=ranks, BENCH_OVERLAP_DELAY_MS=delay_ms)
    try:
        p = subprocess.run([_sys.executable, "-c", _OVERLAP_DRIVER],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        if p.returncode != 0:
            return {"overlap_error": p.stdout[-200:] + p.stderr[-200:]}
        return json.loads(p.stdout.strip().splitlines()[-1])
    except Exception as exc:  # noqa: BLE001
        return {"overlap_error": repr(exc)[:200]}


# ---------------------------------------------------------------------- #
# quantized-wire benchmark (ISSUE 14): throttled-link dpotrf over REAL   #
# TCP sockets, lossless vs bf16 vs int8 wire codecs                      #
# ---------------------------------------------------------------------- #
def bench_qwire_inner(n=256, nb=64, delay_ms=2, chunk_bytes=8192) -> dict:
    """BENCH_MODE=qwire payload: the SAME 2-rank classic-runtime dpotrf
    over REAL loopback TCP sockets on a throttled link (every message
    pays an injected ``delay_ms`` sleep), once per wire codec leg —
    lossless (``comm_quantize`` unset), blockwise bf16, and
    int8-with-per-block-scale. Reports per leg: wall, payload bytes on
    the wire (chunked bulk bytes — what the codec shrinks), the
    per-link labeled reduction ratio, and the factor's relative
    residual vs numpy. The lossless leg runs TWICE and its tiles are
    compared BIT-FOR-BIT — the knob-unset differential the acceptance
    gate rides (quantization off must change nothing)."""
    import concurrent.futures as cf
    from contextlib import ExitStack

    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.comm.tcp import TCPCommEngine, free_ports
    from parsec_tpu.ops import dpotrf_taskpool, make_spd
    from parsec_tpu.utils.params import params as _params

    ranks = 2
    M = make_spd(n, dtype=np.float32)

    def run_once(codec):
        overrides = {
            "comm_chunk_bytes": str(chunk_bytes),
            "comm_quantize": codec,
            "comm_mesh_local": "0",   # payloads must ride the wire
            "ft_inject": f"delay:pct=100:ms={delay_ms}",
        }
        ports = free_ports(ranks)
        eps = [("127.0.0.1", p) for p in ports]
        with ExitStack() as st:
            for k, v in overrides.items():
                st.enter_context(_params.cmdline_override(k, v))

            def rank_fn(r):
                ce = TCPCommEngine(r, eps)
                eng = RemoteDepEngine(ce)
                ctx = parsec_tpu.Context(nb_cores=1, comm=eng)
                try:
                    t0 = time.perf_counter()
                    coll = TwoDimBlockCyclic(
                        n, n, nb, nb, dtype=np.float32,
                        P=ranks, Q=1, nodes=ranks, rank=r)
                    coll.name = "descA"
                    coll.from_numpy(M.copy())
                    tp = dpotrf_taskpool(coll, rank=r, nb_ranks=ranks)
                    ctx.add_taskpool(tp)
                    ctx.wait()
                    wall = time.perf_counter() - t0
                    peer = (r + 1) % ranks
                    stats = {
                        "wall": wall,
                        "chunk_bytes": ce.wire_stats["chunk_bytes_sent"],
                        "bytes_prequant":
                            ce.wire_stats["bytes_prequant"],
                        "bytes_postquant":
                            ce.wire_stats["bytes_postquant"],
                        "bufs_quantized":
                            ce.wire_stats["bufs_quantized"],
                        "codec_ratio": (
                            ce.codec_ratio(peer, "q" + codec)
                            if codec else 1.0),
                    }
                    owned = {c: np.asarray(
                        coll.data_of(*c).sync_to_host().payload)
                        for c in coll.tiles() if coll.rank_of(*c) == r}
                    return stats, owned
                finally:
                    ctx.fini()

            with cf.ThreadPoolExecutor(ranks) as ex:
                results = list(ex.map(rank_fn, range(ranks)))
        tiles = {}
        for (_s, owned) in results:
            tiles.update(owned)
        L = np.zeros((n, n), np.float32)
        for (tm, tk), t in tiles.items():
            L[tm * nb:tm * nb + t.shape[0],
              tk * nb:tk * nb + t.shape[1]] = t
        Lt = np.tril(L).astype(np.float64)
        resid = float(np.abs(Lt @ Lt.T - M).max() / np.abs(M).max())
        agg = {
            "wall_s": round(max(s["wall"] for s, _t in results), 3),
            "wire_payload_bytes": sum(s["chunk_bytes"]
                                      for s, _t in results),
            "bytes_prequant": sum(s["bytes_prequant"]
                                  for s, _t in results),
            "bytes_postquant": sum(s["bytes_postquant"]
                                   for s, _t in results),
            "bufs_quantized": sum(s["bufs_quantized"]
                                  for s, _t in results),
            "codec_ratios": [s["codec_ratio"] for s, _t in results],
            "residual": resid,
        }
        return agg, tiles

    out = {"qwire_n": n, "qwire_nb": nb, "qwire_ranks": ranks,
           "qwire_link_delay_ms": delay_ms,
           "qwire_chunk_bytes": chunk_bytes}
    base, tiles_a = run_once("")
    _base2, tiles_b = run_once("")
    out["qwire_unset_bit_identical"] = bool(
        set(tiles_a) == set(tiles_b)
        and all((tiles_a[c] == tiles_b[c]).all() for c in tiles_a))
    out.update({f"lossless_{k}": v for k, v in base.items()})
    for codec in ("bf16", "int8"):
        leg, _tiles = run_once(codec)
        out.update({f"{codec}_{k}": v for k, v in leg.items()})
        out[f"{codec}_bytes_vs_lossless"] = round(
            leg["wire_payload_bytes"]
            / max(1, base["wire_payload_bytes"]), 4)
    return out


_QWIRE_DRIVER = r"""
import json, os, sys
sys.path.insert(0, os.environ["BENCH_REPO"])
import bench

print(json.dumps(bench.bench_qwire_inner(
    n=int(os.environ.get("BENCH_QWIRE_N", "256")),
    nb=int(os.environ.get("BENCH_QWIRE_NB", "64")),
    delay_ms=int(os.environ.get("BENCH_QWIRE_DELAY_MS", "2")))))
"""


def bench_qwire(n=256, nb=64, delay_ms=2) -> dict:
    """BENCH_MODE=qwire: the quantized-wire legs in a scrubbed CPU
    subprocess (same pattern as bench_overlap: numbers are host
    measurements and must not depend on the parent's chip)."""
    import subprocess
    import sys as _sys

    env = _scrubbed_bench_env(
        n_devices=2,
        BENCH_QWIRE_N=n, BENCH_QWIRE_NB=nb,
        BENCH_QWIRE_DELAY_MS=delay_ms)
    try:
        p = subprocess.run([_sys.executable, "-c", _QWIRE_DRIVER],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        if p.returncode != 0:
            return {"qwire_error": p.stdout[-200:] + p.stderr[-200:]}
        return json.loads(p.stdout.strip().splitlines()[-1])
    except Exception as exc:  # noqa: BLE001
        return {"qwire_error": repr(exc)[:200]}


# ---------------------------------------------------------------------- #
# device-plane + redistribution planner benchmark (ISSUE 19): the        #
# per-tile GET storm vs the planned alltoall reshard vs the device-plane #
# payload route, plus the two-level vs flat lane reduce                  #
# ---------------------------------------------------------------------- #
def bench_dplane_inner(n=64, tile=8, ranks=4) -> dict:
    """BENCH_MODE=dplane payload: the SAME whole-matrix P x 1 -> 1 x Q
    reshard of an ``n x n`` f64 matrix over REAL loopback TCP engines,
    three legs:

    - storm: classic DTD redistribute (one task + GET rendezvous per
      target tile) — the per-tile baseline;
    - planned: ``xfer_collective_redist`` routes the same reshard
      through the xfer/plan.py alltoall rounds (same-(src,dst) tiles
      coalesced into one transfer each);
    - dplane: planned + ``xfer_dplane`` with a DeviceDataPlane on the
      loopback transfer backend — bulk payload leaves the session
      wire, only descriptor/ack control rides it.

    Reports per leg: wall, host-TCP wire bytes (the engine fabric's
    ``bytes_count`` delta around the reshard), reshard MB/s over the
    logical payload volume, and for the planner legs the round/
    transfer counts vs the per-tile move count.  All three legs must
    land BIT-IDENTICAL tiles (reshard traffic is lossless by
    contract).  A fourth, link-free leg times the hierarchical
    ``two_level_allreduce`` against the flat quantize-every-
    contribution reduction at equal residual semantics (both land the
    wire-exact bf16 codec; the hierarchy pays ONE boundary hop per
    group instead of one per contribution)."""
    import concurrent.futures as cf
    from contextlib import ExitStack

    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.collections.redistribute import redistribute
    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.comm.tcp import TCPCommEngine, free_ports
    from parsec_tpu.utils.params import params as _params
    from parsec_tpu.xfer import build_plan

    src_np = np.random.RandomState(19).rand(n, n)
    payload_mb = src_np.nbytes / 1e6

    def leg(knobs, attach_plane=False):
        import threading as _threading
        ports = free_ports(ranks)
        eps = [("127.0.0.1", p) for p in ports]
        barrier = _threading.Barrier(ranks)
        with ExitStack() as st:
            for k, v in knobs.items():
                st.enter_context(_params.cmdline_override(k, v))

            def rank_fn(r):
                ce = TCPCommEngine(r, eps)
                eng = RemoteDepEngine(ce)
                ctx = parsec_tpu.Context(nb_cores=1, comm=eng,
                                         enable_tpu=False)
                try:
                    if attach_plane:
                        from parsec_tpu.comm.xfer import DeviceDataPlane
                        DeviceDataPlane(ce).exchange(timeout=60.0)
                    Y = TwoDimBlockCyclic(
                        n, n, tile, tile, P=ranks, Q=1, nodes=ranks,
                        rank=r, dtype=np.float64).from_numpy(src_np)
                    T = TwoDimBlockCyclic(
                        n, n, tile, tile, P=1, Q=ranks, nodes=ranks,
                        rank=r, dtype=np.float64).from_numpy(
                            np.zeros((n, n)))
                    barrier.wait(60)
                    b0 = ce.fabric.bytes_count
                    t0 = time.perf_counter()
                    tp = redistribute(Y, T, n, n, context=ctx)
                    wall = time.perf_counter() - t0
                    barrier.wait(60)   # both directions fully flushed
                    stats = {
                        "wall": wall,
                        "host_wire_bytes": ce.fabric.bytes_count - b0,
                        "rounds": getattr(tp, "redist_rounds", 0),
                        "transfers": getattr(tp, "redist_transfers", 0),
                        "dplane": dict(ce.dplane_stats),
                    }
                    owned = {c: np.array(T.tile(*c))
                             for c in T.local_tiles()}
                    return stats, owned
                finally:
                    ctx.fini()

            with cf.ThreadPoolExecutor(ranks) as ex:
                results = list(ex.map(rank_fn, range(ranks)))
        got = np.zeros((n, n))
        for (_s, owned) in results:
            for (m, k), t in owned.items():
                got[m * tile:m * tile + t.shape[0],
                    k * tile:k * tile + t.shape[1]] = t
        agg = {
            "wall_s": round(max(s["wall"] for s, _o in results), 4),
            "host_wire_bytes": sum(s["host_wire_bytes"]
                                   for s, _o in results),
            "rounds": max(s["rounds"] for s, _o in results),
            "transfers": max(s["transfers"] for s, _o in results),
            "dplane_xfers": sum(s["dplane"]["dplane_xfers"]
                                for s, _o in results),
            "dplane_bytes": sum(s["dplane"]["dplane_bytes"]
                                for s, _o in results),
            "mb_s": round(payload_mb
                          / max(max(s["wall"] for s, _o in results),
                                1e-9), 1),
        }
        return agg, got

    # the per-tile transfer count the storm pays — a pure function of
    # the two distributions, identical for every leg
    plan = build_plan(
        TwoDimBlockCyclic(n, n, tile, tile, P=ranks, Q=1, nodes=ranks),
        TwoDimBlockCyclic(n, n, tile, tile, P=1, Q=ranks, nodes=ranks))
    out = {"dplane_n": n, "dplane_tile": tile, "dplane_ranks": ranks,
           "tile_moves": plan.tile_moves,
           "plan_rounds": plan.n_rounds,
           "plan_transfers": plan.n_transfers}

    storm, got_storm = leg({})
    planned, got_planned = leg({"xfer_collective_redist": "1"})
    dplane, got_dplane = leg({"xfer_collective_redist": "1",
                              "xfer_dplane": "1",
                              "xfer_backend": "loopback"},
                             attach_plane=True)
    out.update({f"storm_{k}": v for k, v in storm.items()
                if not k.startswith(("rounds", "transfers", "dplane"))})
    out.update({f"planned_{k}": v for k, v in planned.items()})
    out.update({f"dplane_{k}": v for k, v in dplane.items()})
    out["storm_bit_identical"] = bool(np.array_equal(got_storm, src_np))
    out["planned_bit_identical"] = bool(
        np.array_equal(got_planned, src_np))
    out["dplane_bit_identical"] = bool(np.array_equal(got_dplane, src_np))
    out["planned_bytes_vs_storm"] = round(
        planned["host_wire_bytes"] / max(1, storm["host_wire_bytes"]), 4)
    out["dplane_host_bytes_vs_planned"] = round(
        dplane["host_wire_bytes"]
        / max(1, planned["host_wire_bytes"]), 4)

    # link-free two-level vs flat lane reduce at equal codec semantics
    from parsec_tpu.parallel.mesh import (reduced_precision_sum,
                                          two_level_allreduce)
    rng = np.random.RandomState(23)
    shards = [rng.randn(1 << 18).astype(np.float32) for _ in range(8)]
    g = 2
    reduced_precision_sum(shards[:2], "bf16")          # jit warmup
    t0 = time.perf_counter()
    flat = reduced_precision_sum(shards, "bf16")
    flat_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    two = two_level_allreduce(shards, g, "bf16")
    two_s = time.perf_counter() - t0
    out["twolevel_flat_ms"] = round(flat_s * 1e3, 2)
    out["twolevel_ms"] = round(two_s * 1e3, 2)
    out["twolevel_flat_qdq_hops"] = len(shards)
    out["twolevel_qdq_hops"] = (len(shards) + g - 1) // g
    out["twolevel_results_differ"] = bool(not np.array_equal(flat, two))
    return out


_DPLANE_DRIVER = r"""
import json, os, sys
sys.path.insert(0, os.environ["BENCH_REPO"])
import bench

print(json.dumps(bench.bench_dplane_inner(
    n=int(os.environ.get("BENCH_DPLANE_N", "64")),
    tile=int(os.environ.get("BENCH_DPLANE_TILE", "8")),
    ranks=int(os.environ.get("BENCH_DPLANE_RANKS", "4")))))
"""


def bench_dplane(n=64, tile=8, ranks=4) -> dict:
    """BENCH_MODE=dplane: the reshard legs in a scrubbed CPU
    subprocess (same pattern as bench_qwire: numbers are host
    measurements and must not depend on the parent's chip)."""
    import subprocess
    import sys as _sys

    env = _scrubbed_bench_env(
        n_devices=2,
        BENCH_DPLANE_N=n, BENCH_DPLANE_TILE=tile,
        BENCH_DPLANE_RANKS=ranks)
    try:
        p = subprocess.run([_sys.executable, "-c", _DPLANE_DRIVER],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        if p.returncode != 0:
            return {"dplane_error": p.stdout[-200:] + p.stderr[-200:]}
        return json.loads(p.stdout.strip().splitlines()[-1])
    except Exception as exc:  # noqa: BLE001
        return {"dplane_error": repr(exc)[:200]}


# ---------------------------------------------------------------------- #
# cross-rank flow tracing benchmark (ISSUE 15): throttled-TCP dpotrf,    #
# obs_flow off vs on + the knob-unset wire byte-capture differential     #
# ---------------------------------------------------------------------- #
def _dpotrf_task_count(nt: int) -> int:
    """POTRF + TRSM + SYRK + GEMM instance count of a tiled dpotrf."""
    return (nt + nt * (nt - 1)            # potrf + trsm&syrk (pairs)
            + nt * (nt - 1) * (nt - 2) // 6)


def bench_trace_capture_identity() -> dict:
    """The knob-unset wire differential of ISSUE 15's acceptance gate:
    a SCRIPTED deterministic message exchange (sequential sends, one
    frame per message, drained between sends so frame order is
    enqueue order) between two fresh TCP engines, with every outbound
    frame captured at the ``_sendall_vec`` seam.  Three legs:

    - A/B: ``obs_flow`` unset twice — the captured DATA frame streams
      must be BYTE-IDENTICAL (the knob-unset wire is deterministic and
      carries no trace bytes);
    - C: ``obs_flow`` SET on rank 0 only — rank 1 (knob unset) never
      advertises ``"tr"``, so rank 0 negotiates DOWN and its data
      frames stay byte-identical to the unset legs (the mixed-version
      contract).  HELLO frames differ by the advertisement (the same
      precedent as the "rs"/"qz" capabilities) and are excluded.
    - D (ISSUE 16): ``obs_live`` SET on rank 0 only — the same
      contract for the streaming health monitor's knob: rank 1 never
      advertises ``"lv"`` (nor ``"tr"``), so neither plain nor
      EXTENDED trace contexts travel and rank 0's data frames stay
      byte-identical to the unset legs.
    - E (ISSUE 17): ``tune_auto`` SET on rank 0 only — the self-tuning
      controller's knob: rank 1 never advertises ``"tn"``, so no
      K_TUNE renegotiation may ever travel and rank 0's data frames
      stay byte-identical to the unset legs (the tune-on leg proves
      the UNSET legs carry no tuning bytes either way).
    - F (ISSUE 18): ``serve`` SET on rank 0 only, with a session
      server's tenant map armed on the flow allocator — rank 1 never
      advertises ``"sv"`` (nor ``"lv"``), so neither tenant-extended
      trace contexts nor serve control frames may travel and rank 0's
      data frames stay byte-identical to the unset legs.
    - G (ISSUE 19): ``xfer_dplane`` SET on rank 0 only — the device
      data plane's knob: rank 1 never advertises ``"dp"``, so the link
      negotiates DOWN to the session wire and rank 0's data frames
      stay byte-identical to the unset legs (no transfer-server
      address exchange, no descriptor envelopes).
    - H (ISSUE 20): ``stage_compile_xrank``'s "xs" capability SET on
      rank 0 only — rank 1 never advertises the process token, so
      rank 0 negotiates DOWN and no cross-rank digest/boundary control
      frames may travel; data frames stay byte-identical to the unset
      legs.
    """
    import threading as _threading
    from contextlib import ExitStack

    from parsec_tpu.comm import tcp as tcpmod
    from parsec_tpu.comm.engine import (TAG_ACTIVATE, TAG_DTD_DATA,
                                        TAG_MEM_PUT)
    from parsec_tpu.comm.tcp import TCPCommEngine, free_ports
    from parsec_tpu.utils.params import params as _params

    chunk = 4096

    def leg(flow_r0, live_r0=False, tune_r0=False, serve_r0=False,
            dplane_r0=False, xstage_r0=False):
        captured = {}
        orig = tcpmod._sendall_vec

        def capturing(sock, pieces):
            body = b"".join(bytes(p) for p in pieces)
            captured.setdefault(
                _threading.current_thread().name, []).append(body)
            orig(sock, pieces)

        ports = free_ports(2)
        eps = [("127.0.0.1", p) for p in ports]
        with ExitStack() as st:
            st.enter_context(_params.cmdline_override(
                "comm_coalesce_max_bytes", "0"))   # one frame/message
            st.enter_context(_params.cmdline_override(
                "comm_chunk_bytes", str(chunk)))
            tcpmod._sendall_vec = capturing
            try:
                engines = [None, None]

                def boot(r):
                    engines[r] = TCPCommEngine(
                        r, eps, obs_flow=(flow_r0 and r == 0),
                        obs_live=(live_r0 and r == 0),
                        tune_auto=(tune_r0 and r == 0),
                        serve=(serve_r0 and r == 0),
                        dplane=(dplane_r0 and r == 0),
                        xstage=(xstage_r0 and r == 0))
                ts = [_threading.Thread(target=boot, args=(r,))
                      for r in (0, 1)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(30)
                e0, e1 = engines
                # the flow allocator would be armed by the obs wiring;
                # arm it directly here (no Context in this scripted leg)
                if flow_r0 or live_r0 or serve_r0:
                    from parsec_tpu.comm.engine import FlowIds
                    e0._flow = FlowIds(0)
                    e0._flow.live = live_r0 or serve_r0
                    if serve_r0:
                        # what SessionServer installs: a pool the
                        # server owns — the stamp may only travel on
                        # a mutually-negotiated "sv" link
                        e0._flow.tenants = {0: "acme"}

                    class _NullObs:
                        def am_sent(self, *a):
                            pass

                        def flow_sent(self, *a):
                            pass
                    e0._obs = _NullObs()
                rng = np.random.RandomState(7)
                small = rng.rand(16, 16)
                big = rng.rand(64, 64)        # > chunk: rides the bulk lane

                def drained(eng, peer):
                    p = eng._peer_to(peer)
                    deadline = time.time() + 10
                    while time.time() < deadline:
                        with p.cond:
                            if not p.ctrl and not p.bulk:
                                return
                        time.sleep(0.002)
                    raise TimeoutError("send queue never drained")

                msgs = [
                    (TAG_ACTIVATE, {"tp_id": 0, "root": 0, "ranks": [1],
                                    "edges": {1: []}, "data": small}),
                    (TAG_DTD_DATA, {"tp_id": 0, "tile": (0, 0), "seq": 1,
                                    "data": small * 2}),
                    (TAG_MEM_PUT, {"tp_id": 0, "coll": "descA",
                                   "args": (1, 0), "data": big}),
                    (TAG_ACTIVATE, {"tp_id": 0, "root": 0, "ranks": [1],
                                    "edges": {1: []}, "data": big + 1}),
                ]
                for tag, payload in msgs:
                    e0.send_am(1, tag, payload)
                    drained(e0, 1)
                # frames rank 0's writer actually put on the wire,
                # HELLO (the capability advertisement) excluded
                frames = []
                for name, bodies in captured.items():
                    if "tcp-send-r0" in name:
                        frames.extend(
                            b for b in bodies
                            if not (len(b) > 8 and b[8] == 3))  # K_HELLO
                e0.fini()
                e1.fini()
                return frames
            finally:
                tcpmod._sendall_vec = orig

    a = leg(False)
    b = leg(False)
    c = leg(True)
    d = leg(False, live_r0=True)
    e = leg(False, tune_r0=True)
    f = leg(False, serve_r0=True)
    g = leg(False, dplane_r0=True)
    h = leg(False, xstage_r0=True)
    return {
        "trace_frames_captured": len(a),
        "trace_unset_bit_identical": bool(a and a == b),
        "trace_mixed_version_bit_identical": bool(a and a == c),
        "live_mixed_version_bit_identical": bool(a and a == d),
        "tune_mixed_version_bit_identical": bool(a and a == e),
        "serve_mixed_version_bit_identical": bool(a and a == f),
        "dplane_mixed_version_bit_identical": bool(a and a == g),
        # ISSUE 20: "xs" SET on rank 0 only — rank 1 never advertises
        # the token, rank 0 negotiates DOWN and no cross-rank control
        # frames may travel; data frames stay byte-identical
        "xstage_mixed_version_bit_identical": bool(a and a == h),
    }


def bench_trace_inner(n=256, nb=64, delay_ms=3, chunk_bytes=8192) -> dict:
    """BENCH_MODE=trace payload: the SAME 2-rank classic-runtime dpotrf
    over REAL loopback TCP sockets on a throttled link (every data
    message pays an injected ``delay_ms`` sleep; heartbeat/clock pings
    stay sharp), flow tracing OFF vs ON.  The ON leg profiles, merges
    the two rank traces onto one offset-corrected timeline, and
    stitches the cross-rank flow edges; reported deltas are the cost
    of the tracing itself (µs/task, wire bytes per message).  The
    scripted byte-capture differential (``obs_flow`` unset / mixed-
    version peer => bit-identical data frames) rides along."""
    import concurrent.futures as cf
    import tempfile
    from contextlib import ExitStack

    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.comm.tcp import TCPCommEngine, free_ports
    from parsec_tpu.obs import analyze, merge_trace_docs
    from parsec_tpu.ops import dpotrf_taskpool, make_spd
    from parsec_tpu.utils.params import params as _params

    ranks = 2
    M = make_spd(n, dtype=np.float32)
    ntasks = _dpotrf_task_count((n + nb - 1) // nb)

    def run_once(flow, prefix=None):
        overrides = {
            "comm_chunk_bytes": str(chunk_bytes),
            "comm_mesh_local": "0",   # payloads must ride the wire
            "ft_inject": f"delay:pct=100:ms={delay_ms}",
            "obs_flow": "1" if flow else "0",
        }
        if prefix is not None:
            overrides["profile"] = prefix
        ports = free_ports(ranks)
        eps = [("127.0.0.1", p) for p in ports]
        with ExitStack() as st:
            for k, v in overrides.items():
                st.enter_context(_params.cmdline_override(k, v))

            def rank_fn(r):
                ce = TCPCommEngine(r, eps)
                eng = RemoteDepEngine(ce)
                ctx = parsec_tpu.Context(nb_cores=1, comm=eng)
                try:
                    t0 = time.perf_counter()
                    coll = TwoDimBlockCyclic(
                        n, n, nb, nb, dtype=np.float32,
                        P=ranks, Q=1, nodes=ranks, rank=r)
                    coll.name = "descA"
                    coll.from_numpy(M.copy())
                    tp = dpotrf_taskpool(coll, rank=r, nb_ranks=ranks)
                    ctx.add_taskpool(tp)
                    ctx.wait()
                    wall = time.perf_counter() - t0
                    if flow:
                        # a breath for the clock sampler's last pongs,
                        # so the exported offsets rest on several
                        # midpoint samples
                        time.sleep(0.3)
                    stats = {
                        "wall": wall,
                        "msgs": ce.fabric.msg_count,
                        "bytes": ce.fabric.bytes_count,
                        "offsets": dict(ce.clock_offsets_us()),
                    }
                    return stats
                finally:
                    ctx.fini()

            with cf.ThreadPoolExecutor(ranks) as ex:
                return list(ex.map(rank_fn, range(ranks)))

    out = {"trace_n": n, "trace_nb": nb, "trace_ranks": ranks,
           "trace_link_delay_ms": delay_ms, "trace_tasks": ntasks}
    run_once(False)   # warmup: kernel compiles
    off = run_once(False)
    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "trace_bench")
        on = run_once(True, prefix=prefix)
        docs = []
        for r in range(ranks):
            with open(f"{prefix}.rank{r}.trace.json") as fh:
                docs.append(json.load(fh))
        merged = merge_trace_docs(docs)
        report = analyze([merged])
    cr = report.get("cross_rank") or {}
    out["trace_off_wall_s"] = round(max(s["wall"] for s in off), 3)
    out["trace_on_wall_s"] = round(max(s["wall"] for s in on), 3)
    out["trace_us_per_task_off"] = round(
        out["trace_off_wall_s"] / ntasks * 1e6, 2)
    out["trace_us_per_task_on"] = round(
        out["trace_on_wall_s"] / ntasks * 1e6, 2)
    out["trace_us_per_task_delta"] = round(
        out["trace_us_per_task_on"] - out["trace_us_per_task_off"], 2)
    bpm_off = (sum(s["bytes"] for s in off)
               / max(1, sum(s["msgs"] for s in off)))
    bpm_on = (sum(s["bytes"] for s in on)
              / max(1, sum(s["msgs"] for s in on)))
    out["trace_wire_bytes_per_msg_off"] = round(bpm_off, 1)
    out["trace_wire_bytes_per_msg_on"] = round(bpm_on, 1)
    out["trace_added_wire_bytes_per_msg"] = round(bpm_on - bpm_off, 1)
    out["trace_flow_edges"] = cr.get("flow_edges", 0)
    out["trace_edges_per_link"] = cr.get("edges_per_link", {})
    out["trace_unmatched_flows"] = cr.get("unmatched_flows", -1)
    out["trace_min_lag_us"] = cr.get("min_lag_us")
    out["trace_negative_lag_edges"] = cr.get("negative_lag_edges", -1)
    dcp = cr.get("critical_path") or {}
    out["trace_critpath_cross_edges"] = dcp.get("cross_edges", 0)
    out["trace_per_link_exposed_us"] = cr.get("per_link_exposed_us", {})
    out["trace_clock_offsets_us"] = [s["offsets"] for s in on]
    out.update(bench_trace_capture_identity())
    return out


_TRACE_DRIVER = r"""
import json, os, sys
sys.path.insert(0, os.environ["BENCH_REPO"])
import bench

print(json.dumps(bench.bench_trace_inner(
    n=int(os.environ.get("BENCH_TRACE_N", "256")),
    nb=int(os.environ.get("BENCH_TRACE_NB", "64")),
    delay_ms=int(os.environ.get("BENCH_TRACE_DELAY_MS", "3")))))
"""


def bench_trace(n=256, nb=64, delay_ms=3) -> dict:
    """BENCH_MODE=trace: the flow-tracing off/on legs in a scrubbed CPU
    subprocess (same pattern as bench_qwire: numbers are host
    measurements and must not depend on the parent's chip)."""
    import subprocess
    import sys as _sys

    env = _scrubbed_bench_env(
        n_devices=2,
        BENCH_TRACE_N=n, BENCH_TRACE_NB=nb,
        BENCH_TRACE_DELAY_MS=delay_ms)
    try:
        p = subprocess.run([_sys.executable, "-c", _TRACE_DRIVER],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        if p.returncode != 0:
            return {"trace_error": p.stdout[-200:] + p.stderr[-200:]}
        return json.loads(p.stdout.strip().splitlines()[-1])
    except Exception as exc:  # noqa: BLE001
        return {"trace_error": repr(exc)[:200]}


# ---------------------------------------------------------------------- #
# multi-tenant serving benchmark (ISSUE 18): weighted-fair latency      #
# tenant vs a bulk saturator on ONE persistent context                  #
# ---------------------------------------------------------------------- #
_SERVE_DRIVER = r"""
import json, os, sys, threading, time
sys.path.insert(0, os.environ["BENCH_REPO"])
import parsec_tpu
from parsec_tpu import dtd
from parsec_tpu.dsl.dtd import VALUE
from parsec_tpu.serve import SessionServer
from parsec_tpu.utils.params import params

POOLS = int(os.environ.get("BENCH_SERVE_POOLS", "32"))
BULK_TASKS = int(os.environ.get("BENCH_SERVE_BULK_TASKS", "24"))
LAT_TASKS = int(os.environ.get("BENCH_SERVE_LAT_TASKS", "4"))
SPIN_S = float(os.environ.get("BENCH_SERVE_SPIN_MS", "1.0")) / 1e3


def mk_build(n_tasks):
    def build():
        tp = dtd.taskpool_new()

        def body(es, task):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < SPIN_S:
                pass

        for k in range(n_tasks):
            tp.insert_task(body, (k, VALUE))
        return tp
    return build


def leg(fair):
    # one persistent context, a weight-1 bulk tenant saturating it, a
    # weight-8 latency tenant probing it; fair=False disables the
    # deficit fold (ctx.serve_fairness = None): pure arrival-order
    # FIFO, the baseline the weighted leg is judged against
    with params.cmdline_override("serve", "1"):
        ctx = parsec_tpu.init(nb_cores=2, scheduler="spq",
                              enable_tpu=False)
        srv = SessionServer(ctx)
        if not fair:
            ctx.serve_fairness = None
        srv.open_tenant("bulk", weight=1)
        srv.open_tenant("latency", weight=8)
        stop = threading.Event()
        fail = []

        def bulk_pump():
            try:
                while not stop.is_set():
                    subs = [srv.submit("bulk", mk_build(BULK_TASKS),
                                       ntasks=BULK_TASKS)
                            for _ in range(4)]
                    for s in subs:
                        s.wait(120)
            except Exception as exc:
                fail.append(repr(exc))

        th = threading.Thread(target=bulk_pump, daemon=True)
        th.start()
        time.sleep(0.3)            # let the backlog build
        lats = []
        for _ in range(POOLS):
            sub = srv.submit("latency", mk_build(LAT_TASKS),
                             ntasks=LAT_TASKS)
            if not sub.wait(120):
                fail.append("latency pool timed out")
                break
            lats.append(sub.lat_us)
        stop.set()
        th.join(120)
        st = srv.stats()["tenants"]
        done = {t: c["pools_done"] for t, c in st.items()}
        srv.close()
        ctx.fini()
        if fail or not lats:
            raise RuntimeError(f"serve leg failed: {fail[:3]}")
        lats.sort()
        p50 = lats[len(lats) // 2]
        p99 = lats[min(len(lats) - 1, int(round(0.99 * len(lats))))]
        return p50, p99, done


fifo_p50, fifo_p99, fifo_done = leg(fair=False)
w_p50, w_p99, w_done = leg(fair=True)
total = max(1, sum(w_done.values()))
print(json.dumps({
    "serve_latency_p50_us_fifo": round(fifo_p50, 1),
    "serve_latency_p99_us_fifo": round(fifo_p99, 1),
    "serve_latency_p50_us_weighted": round(w_p50, 1),
    "serve_latency_p99_us_weighted": round(w_p99, 1),
    "serve_weighted_p99_vs_fifo": round(w_p99 / max(fifo_p99, 1e-9), 3),
    "serve_bulk_pools_done": w_done.get("bulk", 0),
    "serve_latency_pools_done": w_done.get("latency", 0),
    "serve_latency_pool_share": round(
        w_done.get("latency", 0) / total, 3),
}))
"""


def bench_serve() -> dict:
    """BENCH_MODE=serve (ISSUE 18): a weight-8 latency tenant probing
    one persistent context that a weight-1 bulk tenant saturates, in a
    scrubbed CPU subprocess.  The FIFO leg (deficit fold disabled) is
    the baseline; the weighted leg's per-tenant p50/p99 and pool share
    show what the fairness boost buys the SLO tenant.  Link
    independent — rides every bench_all record."""
    import subprocess
    import sys as _sys

    env = _scrubbed_bench_env(n_devices=2)
    try:
        p = subprocess.run([_sys.executable, "-c", _SERVE_DRIVER],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        if p.returncode != 0:
            return {"serve_error": p.stdout[-200:] + p.stderr[-200:]}
        return json.loads(p.stdout.strip().splitlines()[-1])
    except Exception as exc:  # noqa: BLE001
        return {"serve_error": repr(exc)[:200]}


def bench_health_inner(n=256, nb=64, delay_ms=3, chunk_bytes=8192) -> dict:
    """BENCH_MODE=health payload (ISSUE 16): the SAME 2-rank throttled-
    TCP dpotrf as the trace bench, streaming health monitor OFF vs ON —
    the reported delta is the us/task cost of obs_live itself (span
    folding, window ticks, flow-lag stitching).  A third leg measures
    DETECTOR LATENCY: run one clean dpotrf to warm the baselines, then
    swap rank 1's fault injector mid-run so its sends suddenly pay a
    4x delay, and report how long until rank 0's monitor fires on the
    inbound link."""
    import concurrent.futures as cf
    import threading as _threading
    from contextlib import ExitStack

    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.comm.tcp import TCPCommEngine, free_ports
    from parsec_tpu.ft.inject import FaultInjector
    from parsec_tpu.ops import dpotrf_taskpool, make_spd
    from parsec_tpu.utils.params import params as _params

    ranks = 2
    M = make_spd(n, dtype=np.float32)
    ntasks = _dpotrf_task_count((n + nb - 1) // nb)

    def run_once(live, detector=False):
        overrides = {
            "comm_chunk_bytes": str(chunk_bytes),
            "comm_mesh_local": "0",   # payloads must ride the wire
            "obs_live": "1" if live else "0",
        }
        if detector:
            # fast windows so the latency reflects the detector, not
            # the sampling cadence; the straggler is injected mid-run
            overrides["obs_live_window_ms"] = "50"
        else:
            overrides["ft_inject"] = f"delay:pct=100:ms={delay_ms}"
        ports = free_ports(ranks)
        eps = [("127.0.0.1", p) for p in ports]
        barrier = _threading.Barrier(ranks)
        onset = [0.0]
        with ExitStack() as st:
            for k, v in overrides.items():
                st.enter_context(_params.cmdline_override(k, v))

            def rank_fn(r):
                ce = TCPCommEngine(r, eps)
                eng = RemoteDepEngine(ce)
                ctx = parsec_tpu.Context(nb_cores=1, comm=eng)
                try:
                    def rep(name):
                        coll = TwoDimBlockCyclic(
                            n, n, nb, nb, dtype=np.float32,
                            P=ranks, Q=1, nodes=ranks, rank=r)
                        coll.name = name
                        coll.from_numpy(M.copy())
                        tp = dpotrf_taskpool(coll, rank=r, nb_ranks=ranks)
                        ctx.add_taskpool(tp)
                        ctx.wait()

                    t0 = time.perf_counter()
                    rep("descA")
                    wall = time.perf_counter() - t0
                    firing = None
                    if detector:
                        # quiet windows after descA converge the per-
                        # link baselines (warmup_windows) so the descB
                        # spike is judged against a warm EWMA — on a
                        # fast host descA alone spans too few windows
                        time.sleep(0.7)
                        if r == 1:
                            # mid-run regression: rank 1's data sends
                            # suddenly pay a 4x delay — rank 0's inbound
                            # exposed-wait baseline (warmed by descA)
                            # should blow past its z threshold
                            ce._ft = FaultInjector.from_spec(
                                f"delay:pct=100:ms={delay_ms * 4}", rank=1)
                        else:
                            onset[0] = time.time()
                        barrier.wait(timeout=120)
                        rep("descB")
                        barrier.wait(timeout=120)
                        time.sleep(0.4)  # a few detector windows
                        if r == 0 and ctx.obs.live is not None:
                            snap = ctx.obs.live.snapshot()
                            for f in snap.get("firings", []):
                                if f.get("ts", 0.0) >= onset[0]:
                                    firing = f
                                    break
                    return {"wall": wall, "firing": firing,
                            "onset": onset[0]}
                finally:
                    ctx.fini()

            with cf.ThreadPoolExecutor(ranks) as ex:
                return list(ex.map(rank_fn, range(ranks)))

    out = {"health_n": n, "health_nb": nb, "health_ranks": ranks,
           "health_link_delay_ms": delay_ms, "health_tasks": ntasks}
    run_once(False)   # warmup: kernel compiles
    off = run_once(False)
    on = run_once(True)
    out["health_off_wall_s"] = round(max(s["wall"] for s in off), 3)
    out["health_on_wall_s"] = round(max(s["wall"] for s in on), 3)
    out["health_us_per_task_off"] = round(
        out["health_off_wall_s"] / ntasks * 1e6, 2)
    out["health_us_per_task_on"] = round(
        out["health_on_wall_s"] / ntasks * 1e6, 2)
    out["health_us_per_task_delta"] = round(
        out["health_us_per_task_on"] - out["health_us_per_task_off"], 2)
    det = run_once(True, detector=True)
    firing = det[0].get("firing")
    if firing is not None:
        out["health_detector_latency_s"] = round(
            firing["ts"] - det[0]["onset"], 3)
        out["health_detector_kind"] = firing.get("kind")
        out["health_detector_link"] = firing.get("link")
        out["health_detector_suspect"] = firing.get("suspect")
    else:
        out["health_detector_latency_s"] = -1.0
        out["health_detector_kind"] = None
    return out


_HEALTH_DRIVER = r"""
import json, os, sys
sys.path.insert(0, os.environ["BENCH_REPO"])
import bench

print(json.dumps(bench.bench_health_inner(
    n=int(os.environ.get("BENCH_HEALTH_N", "256")),
    nb=int(os.environ.get("BENCH_HEALTH_NB", "64")),
    delay_ms=int(os.environ.get("BENCH_HEALTH_DELAY_MS", "3")))))
"""


def bench_health(n=256, nb=64, delay_ms=3) -> dict:
    """BENCH_MODE=health: the obs_live off/on legs in a scrubbed CPU
    subprocess (same pattern as bench_trace: numbers are host
    measurements and must not depend on the parent's chip)."""
    import subprocess
    import sys as _sys

    env = _scrubbed_bench_env(
        n_devices=2,
        BENCH_HEALTH_N=n, BENCH_HEALTH_NB=nb,
        BENCH_HEALTH_DELAY_MS=delay_ms)
    try:
        p = subprocess.run([_sys.executable, "-c", _HEALTH_DRIVER],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        if p.returncode != 0:
            return {"health_error": p.stdout[-200:] + p.stderr[-200:]}
        return json.loads(p.stdout.strip().splitlines()[-1])
    except Exception as exc:  # noqa: BLE001
        return {"health_error": repr(exc)[:200]}


# ---------------------------------------------------------------------- #
# closed-loop self-tuning benchmark (ISSUE 17): throttled asymmetric-    #
# link dpotrf, the tuned run vs each static setting it chose between     #
# ---------------------------------------------------------------------- #
def bench_autotune_inner(n=1024, nb=128, link_mbps=1.0,
                         chunk_bytes=65536, window_ms=20) -> dict:
    """BENCH_MODE=autotune payload (ISSUE 17): a 2-rank classic-runtime
    dpotrf on an ASYMMETRIC link — rank 1's writer is paced to
    ``link_mbps`` (a bytes-proportional sleep around ``_sendall_vec``,
    the same seam the capture-identity differential taps), rank 0
    sends at loopback speed.  The tuned leg (``tune_auto``) starts
    lossless at the default device shape and lets the controller move:
    the send-bandwidth floor escalates rank 1's wire codec up the
    ladder within ``tune_residual_budget`` = 1e-1 (lossless -> qbf16 ->
    qint8), and the occupancy hill-climb reshapes ``batch_max``.

    Every leg runs TWO reps in the same context and the SECOND is the
    measured one: rep 1 is the adaptation window for the tuned leg and
    the jit/baseline warmup for every leg, so all legs pay the same
    per-taskpool compile set and the tuned leg is measured at its
    SETTLED configuration — the steady state an adaptive controller
    actually buys, not its first seconds of exploration.

    The static legs are the settings the controller chose between and
    REJECTED, read back from the tuned run itself: every codec rung it
    climbed through and left (never the one it settled on) crossed
    with both device shapes it touched (the default it abandoned and
    the shape it chose).  The ORACLE leg — the full chosen (codec,
    shape) pinned statically from the start — is reported separately:
    an adaptive run cannot beat the config it converged to, so the
    gate bounds tuned against the oracle (within a few percent) and
    requires it to strictly beat every rejected static."""
    import concurrent.futures as cf
    import threading as _threading
    from contextlib import ExitStack

    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.comm import tcp as tcpmod
    from parsec_tpu.comm.tcp import TCPCommEngine, free_ports
    from parsec_tpu.obs import merge_trace_docs
    from parsec_tpu.obs.spans import HEALTH_STREAM_TID
    from parsec_tpu.ops import dpotrf_taskpool, make_spd
    from parsec_tpu.utils.params import params as _params

    ranks = 2
    batch_default = 16
    budget = 1e-1
    M = make_spd(n, dtype=np.float32)
    bw_bps = float(link_mbps) * 1e6

    real_sendall = tcpmod._sendall_vec

    def paced_sendall(sock, pieces):
        nbytes = sum(len(p) if isinstance(p, (bytes, bytearray))
                     else p.nbytes for p in pieces)
        real_sendall(sock, pieces)
        # asymmetric throttle: only rank 1's writer threads pay the
        # pacing sleep, so its send bandwidth EWMA converges to
        # link_mbps while rank 0's link stays at loopback speed
        if _threading.current_thread().name.startswith("tcp-send-r1"):
            time.sleep(nbytes / bw_bps)

    def run_leg(tune=False, codec="", batch_max=batch_default):
        overrides = {
            "comm_chunk_bytes": str(chunk_bytes),
            "comm_mesh_local": "0",   # payloads must ride the wire
            "device_batch_max": str(batch_max),
        }
        if codec:
            overrides["comm_quantize"] = codec
        if tune:
            overrides.update({
                "tune_auto": "1",
                "tune_residual_budget": f"{budget:g}",
                "obs_live_window_ms": str(window_ms),
            })
        ports = free_ports(ranks)
        eps = [("127.0.0.1", p) for p in ports]
        traces = {}
        with ExitStack() as st:
            for k, v in overrides.items():
                st.enter_context(_params.cmdline_override(k, v))
            tcpmod._sendall_vec = paced_sendall
            try:
                def rank_fn(r):
                    ce = TCPCommEngine(r, eps)
                    eng = RemoteDepEngine(ce)
                    # every leg pays the profiler so walls compare
                    # like-for-like; only the tuned leg's trace is kept
                    ctx = parsec_tpu.Context(nb_cores=1, comm=eng,
                                             profile=True)
                    try:
                        def rep(name):
                            coll = TwoDimBlockCyclic(
                                n, n, nb, nb, dtype=np.float32,
                                P=ranks, Q=1, nodes=ranks, rank=r)
                            coll.name = name
                            coll.from_numpy(M.copy())
                            tp = dpotrf_taskpool(coll, rank=r,
                                                 nb_ranks=ranks)
                            ctx.add_taskpool(tp)
                            ctx.wait()
                            return coll

                        rep("descA")      # adapt (tuned) / warm (all)
                        sent1 = ce.wire_stats["chunk_bytes_sent"]
                        t0 = time.perf_counter()
                        coll = rep("descB")   # the measured rep
                        wall = time.perf_counter() - t0
                        peer = (r + 1) % ranks
                        d = {"wall": wall,
                             "rep2_bytes":
                                 ce.wire_stats["chunk_bytes_sent"]
                                 - sent1,
                             "active": ce.active_quant_codec(peer)}
                        tn = getattr(ctx.obs, "tuner", None)
                        if tn is not None:
                            d["counts"] = dict(tn.counts)
                        d["batch_max"] = [
                            dev.batch_max for dev in ctx.devices
                            if getattr(dev, "device_type", "") == "tpu"]
                        if tune:
                            ctx._stamp_profile_meta()
                            traces[r] = ctx.profile.to_chrome_trace()
                        owned = {c: np.asarray(
                            coll.data_of(*c).sync_to_host().payload)
                            for c in coll.tiles()
                            if coll.rank_of(*c) == r}
                        return d, owned
                    finally:
                        ctx.fini()

                with cf.ThreadPoolExecutor(ranks) as ex:
                    results = list(ex.map(rank_fn, range(ranks)))
            finally:
                tcpmod._sendall_vec = real_sendall
        tiles = {}
        for _d, owned in results:
            tiles.update(owned)
        L = np.zeros((n, n), np.float32)
        for (tm, tk), t in tiles.items():
            L[tm * nb:tm * nb + t.shape[0],
              tk * nb:tk * nb + t.shape[1]] = t
        Lt = np.tril(L).astype(np.float64)
        resid = float(np.abs(Lt @ Lt.T - M).max() / np.abs(M).max())
        leg = {
            "wall_s": round(max(d["wall"] for d, _t in results), 3),
            "residual": resid,
            "r1_rep2_bytes": results[1][0]["rep2_bytes"],
        }
        if tune:
            leg["counts"] = [d.get("counts") for d, _t in results]
            leg["active_codec"] = results[1][0]["active"]
            leg["batch_max_final"] = min(
                min(d["batch_max"]) for d, _t in results
                if d["batch_max"])
            merged = merge_trace_docs([traces[0], traces[1]])
            annos = [e for e in merged["traceEvents"]
                     if e.get("ph") == "i"
                     and e.get("tid") == HEALTH_STREAM_TID
                     and str(e.get("name", "")).startswith("tune:")]
            leg["timeline_annotations"] = sorted(
                {e["name"] for e in annos})
            leg["timeline_annotation_count"] = len(annos)
        return leg

    out = {"autotune_n": n, "autotune_nb": nb,
           "autotune_ranks": ranks,
           "autotune_link_mbps": link_mbps,
           "autotune_chunk_bytes": chunk_bytes,
           "autotune_window_ms": window_ms,
           "autotune_residual_budget": budget,
           "autotune_batch_default": batch_default}

    tuned = run_leg(tune=True)
    out.update({f"tuned_{k}": v for k, v in tuned.items()})

    # the choice set, read back from the tuned run: every rung below
    # the one it settled on, crossed with both shapes it touched
    ladder = [None, "qbf16", "qint8"]
    active = tuned.get("active_codec")
    final_rung = ladder.index(active) if active in ladder else 0
    rejected_codecs = ladder[:final_rung] or [None]
    bstar = tuned.get("batch_max_final", batch_default)
    shapes = sorted({batch_default, bstar})
    out["autotune_chosen_codec"] = active or "lossless"
    out["autotune_chosen_batch_max"] = bstar

    static_walls = {}
    for qc in rejected_codecs:
        for bm in shapes:
            label = f"static_{(qc or 'lossless').lstrip('q')}_b{bm}"
            leg = run_leg(codec=(qc or "").lstrip("q"), batch_max=bm)
            static_walls[label] = leg["wall_s"]
            out.update({f"{label}_{k}": v for k, v in leg.items()})
    oracle = run_leg(codec=(active or "").lstrip("q"), batch_max=bstar)
    out.update({f"oracle_{k}": v for k, v in oracle.items()})

    best_static = min(static_walls.values()) if static_walls else -1.0
    out["autotune_best_static_wall_s"] = best_static
    out["autotune_tuned_vs_best_static"] = round(
        best_static / max(1e-9, tuned["wall_s"]), 3)
    out["autotune_tuned_vs_oracle"] = round(
        tuned["wall_s"] / max(1e-9, oracle["wall_s"]), 3)
    return out


_AUTOTUNE_DRIVER = r"""
import json, os, sys
sys.path.insert(0, os.environ["BENCH_REPO"])
import bench

print(json.dumps(bench.bench_autotune_inner(
    n=int(os.environ.get("BENCH_AUTOTUNE_N", "1024")),
    nb=int(os.environ.get("BENCH_AUTOTUNE_NB", "128")),
    link_mbps=float(os.environ.get("BENCH_AUTOTUNE_LINK_MBPS", "1.0")))))
"""


def bench_autotune(n=1024, nb=128, link_mbps=1.0) -> dict:
    """BENCH_MODE=autotune: the self-tuning legs in a scrubbed CPU
    subprocess (same pattern as bench_health: numbers are host
    measurements and must not depend on the parent's chip)."""
    import subprocess
    import sys as _sys

    env = _scrubbed_bench_env(
        n_devices=2,
        BENCH_AUTOTUNE_N=n, BENCH_AUTOTUNE_NB=nb,
        BENCH_AUTOTUNE_LINK_MBPS=link_mbps)
    try:
        p = subprocess.run([_sys.executable, "-c", _AUTOTUNE_DRIVER],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        if p.returncode != 0:
            return {"autotune_error": p.stdout[-200:] + p.stderr[-200:]}
        return json.loads(p.stdout.strip().splitlines()[-1])
    except Exception as exc:  # noqa: BLE001
        return {"autotune_error": repr(exc)[:200]}


# ---------------------------------------------------------------------- #
# stage-compile benchmark (ISSUE 12): classic-runtime dpotrf through     #
# compiled stages vs the interpreted per-task/batched dispatch           #
# ---------------------------------------------------------------------- #
def bench_stagec_inner(n=768, nb=64, reps=3, cores=1) -> dict:
    """BENCH_MODE=stagec payload: the SAME classic-runtime dpotrf at
    the SAME N/NB, interpreted (``stage_compile`` unset — the exact
    pre-stagec path) vs stage-compiled (stagec/ lowers the verified
    DAG into fused jitted stages executed as single chores).  Tiles are
    prestaged into device memory outside the clock on BOTH legs (the
    bench_runtime steady-state methodology), walls are best-of-reps
    with the compile warm (the AOT stage cache persists across
    taskpools by design), and the factors must be BIT-EXACT across
    legs — the compiled program unrolls the identical per-task
    subgraphs the interpreter dispatches one by one.

    The ISSUE 13 legs (chained dposv, residue-heavy dtrsm) run FIRST:
    their per-task deltas are tens of us and the big dpotrf leg leaves
    the process measurably noisier (heap pressure) than a fresh one."""
    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.ops import dpotrf_taskpool
    from parsec_tpu.utils.params import params as _params

    out = {}
    out.update(bench_stagec_chain_inner(
        n=int(os.environ.get("BENCH_STAGEC_CHAIN_N", "192")),
        nb=64, reps=max(4, reps), cores=cores))
    out.update(bench_stagec_residue_inner(
        n=int(os.environ.get("BENCH_STAGEC_RES_N", "512")),
        nb=32, reps=reps, cores=cores))

    M = make_input(n, np.float32)

    def leg(stagec):
        from contextlib import ExitStack
        with ExitStack() as st:
            if stagec:
                st.enter_context(
                    _params.cmdline_override("stage_compile", "1"))
                st.enter_context(_params.cmdline_override(
                    "stage_compile_max_tasks",
                    os.environ.get("BENCH_STAGEC_MAX_TASKS", "4096")))
            ctx = parsec_tpu.init(nb_cores=cores)
            try:
                import jax
                devs = [d for d in ctx.devices if d.device_type == "tpu"]
                if not devs:
                    return None
                dev = devs[0]
                best = None
                A = None
                for _ in range(max(2, reps)):   # rep 1 pays the compile
                    A = TwoDimBlockCyclic(n, n, nb, nb,
                                          dtype=np.float32
                                          ).from_numpy(M.copy())
                    for co in A.tiles():
                        dev.data_advise(A.data_of(*co), "prefetch")
                    jax.block_until_ready(
                        [A.data_of(*co).get_copy(dev.device_index).payload
                         for co in A.tiles()])
                    t0 = time.perf_counter()
                    ctx.add_taskpool(dpotrf_taskpool(A))
                    ctx.wait()
                    pend = [A.data_of(*co).newest_copy().payload
                            for co in A.tiles()]
                    jax.block_until_ready([p for p in pend
                                 if hasattr(p, "block_until_ready")])
                    dt = time.perf_counter() - t0
                    best = dt if best is None else min(best, dt)
                return best, np.tril(A.to_numpy()), dict(ctx.stage_stats)
            finally:
                ctx.fini()

    interp = leg(False)
    staged = leg(True)
    out.update({"stagec_n": n, "stagec_nb": nb})
    if interp is None or staged is None:
        out["error"] = "no XLA device attached"
        return out
    (ti, Li, _si), (ts, Ls, ss) = interp, staged
    fl = dpotrf_flops(n)
    out["interpreted_gflops"] = round(fl / ti / 1e9, 2)
    out["stagec_gflops"] = round(fl / ts / 1e9, 2)
    out["stagec_speedup"] = round(ti / ts, 2)
    out["stagec_bit_exact_vs_interpreted"] = bool(np.array_equal(Li, Ls))
    resid = float(np.abs(Ls.astype(np.float64)
                         @ Ls.astype(np.float64).T - M).max()
                  / np.abs(M).max())
    out["stagec_residual"] = resid
    out.update({f"stagec_{k}": v for k, v in ss.items()
                if k != "stage_compile_ns"})
    out["stagec_compile_ms"] = round(ss["stage_compile_ns"] / 1e6, 1)
    return out


def bench_stagec_chain_inner(n=192, nb=64, reps=4, cores=1) -> dict:
    """Chained dposv leg (ISSUE 13): the SAME 3-pool composition
    (dpotrf ; trsm_fwd ; trsm_bwd, one RHS panel) four ways —
    interpreted (stage_compile unset), the PR 12 per-pool compiled
    path reproduced exactly (reader classes excluded from lowering via
    ``stage_compile_exclude``, which is what PR 12's STG300 verdict
    did: one fused program per pool, interpreted reader residue, host
    flush between pools), today's relaxed per-pool path (readers fuse,
    chaining off), and CHAINED (stagec/chain.py: both boundaries
    fused, ONE program for the whole solve).

    Methodology: taskpools are constructed OUTSIDE the clock (the
    bench_runtime prestage-outside-the-clock convention — spec->class
    construction is identical across legs and amortizable); the clock
    covers submission to completion, including ``declare_chain`` on
    the chained leg (chain-specific work must pay its way).  Walls are
    best-of-reps with the AOT caches warm; the chained solution must
    be BIT-EXACT vs interpreted.  The headline is
    chain_speedup_vs_pr12_perpool — what cross-pool chaining buys over
    PR 12's per-pool compiled path."""
    import jax
    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.ops import (dpotrf_taskpool, dtrsm_lower_taskpool,
                                dtrsm_lower_trans_taskpool)
    from parsec_tpu.stagec.chain import declare_chain
    from parsec_tpu.utils.params import params as _params

    M = make_input(n, np.float32)
    rng = np.random.RandomState(23)
    B0 = rng.rand(n, nb).astype(np.float32)

    def leg(stagec, chain, exclude=""):
        from contextlib import ExitStack
        with ExitStack() as st:
            if stagec:
                st.enter_context(
                    _params.cmdline_override("stage_compile", "1"))
                st.enter_context(_params.cmdline_override(
                    "stage_compile_max_tasks",
                    os.environ.get("BENCH_STAGEC_MAX_TASKS", "4096")))
            if exclude:
                st.enter_context(_params.cmdline_override(
                    "stage_compile_exclude", exclude))
            if not chain:
                st.enter_context(
                    _params.cmdline_override("stage_compile_chain", "0"))
            ctx = parsec_tpu.init(nb_cores=cores)
            try:
                if not any(d.device_type == "tpu" for d in ctx.devices):
                    return None
                # a 4-6 ms single solve is below this host's timing
                # noise floor: each timed rep clocks `iters`
                # back-to-back solves (pools pre-built OUTSIDE the
                # clock) and reports the mean
                iters = int(os.environ.get("BENCH_STAGEC_CHAIN_ITERS",
                                           "6"))
                best = X = stats0 = None
                for rep in range(1 + max(2, reps)):  # rep 0: compile
                    batch = []
                    for _ in range(1 if rep == 0 else iters):
                        A = TwoDimBlockCyclic(
                            n, n, nb, nb, dtype=np.float32
                            ).from_numpy(M.copy())
                        B = TwoDimBlockCyclic(
                            n, nb, nb, nb, dtype=np.float32
                            ).from_numpy(B0.copy())
                        batch.append((B, [
                            dpotrf_taskpool(A),
                            dtrsm_lower_taskpool(A, B),
                            dtrsm_lower_trans_taskpool(A, B)]))
                    stats0 = dict(ctx.stage_stats)
                    t0 = time.perf_counter()
                    for B, pools in batch:
                        if chain:
                            declare_chain(ctx, pools)
                        for tp_ in pools:
                            ctx.add_taskpool(tp_)
                            ctx.wait()
                        pend = [B.data_of(*co).newest_copy().payload
                                for co in B.tiles()]
                        jax.block_until_ready([p for p in pend
                                     if hasattr(p, "block_until_ready")])
                    dt = (time.perf_counter() - t0) / len(batch)
                    if rep > 0:
                        best = dt if best is None else min(best, dt)
                    X = batch[-1][0].to_numpy()
                delta = {k: (ctx.stage_stats[k] - stats0[k])
                         // len(batch) for k in ctx.stage_stats}
                return best, X, delta
            finally:
                ctx.fini()

    out = {"chain_n": n, "chain_nb": nb}
    interp = leg(False, False)
    pr12 = leg(True, False, exclude="RDIAG,RPANEL")
    perpool = leg(True, False)
    chained = leg(True, True)
    if None in (interp, pr12, perpool, chained):
        out["chain_error"] = "no XLA device attached"
        return out
    (ti, Xi, _si), (t12, X12, _s12) = interp, pr12
    (tp_, Xp, _sp), (tc, Xc, sc) = perpool, chained
    out["chain_interpreted_wall_s"] = round(ti, 4)
    out["chain_pr12_perpool_wall_s"] = round(t12, 4)
    out["chain_perpool_wall_s"] = round(tp_, 4)
    out["chain_chained_wall_s"] = round(tc, 4)
    out["chain_speedup_vs_pr12_perpool"] = round(t12 / tc, 2)
    out["chain_speedup_vs_perpool"] = round(tp_ / tc, 2)
    out["chain_speedup_vs_interpreted"] = round(ti / tc, 2)
    out["chain_links"] = sc["chain_links"]           # final-rep delta
    out["chain_fallbacks"] = sc["chain_fallbacks"]
    out["chain_dispatches"] = sc["stage_dispatches"]
    out["chain_bit_exact_vs_interpreted"] = bool(np.array_equal(Xi, Xc))
    out["chain_perpool_bit_exact"] = bool(
        np.array_equal(Xi, Xp) and np.array_equal(Xi, X12))
    return out


def bench_stagec_residue_inner(n=512, nb=64, reps=3, cores=1) -> dict:
    """Residue-heavy leg (ISSUE 13): the mixed host+device dtrsm
    forward-solve spec (host-owned reader classes, device TRSM/GEMM)
    with GEMM operator-excluded from stage lowering
    (``stage_compile_exclude`` — verdict STG306), so the bulk of the
    DAG runs as device residue BETWEEN compiled TRSM stages.  Measured
    with the compiled residue schedule OFF (PR 12: every residue task
    pays the scheduler round-trip) vs ON (pre-planned per-(level,
    class) groups ride the batched dispatch as one burst) — the
    headline is the us/task drop across the whole solve, residue
    dispatch isolated from fused-stage gains (both legs compile the
    same stages)."""
    import jax
    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.ops import dtrsm_lower_taskpool
    from parsec_tpu.utils.params import params as _params

    M = make_input(n, np.float32)
    Lnp = np.tril(np.linalg.cholesky(M.astype(np.float64))
                  ).astype(np.float32)
    rng = np.random.RandomState(29)
    B0 = rng.rand(n, nb).astype(np.float32)
    nt = (n + nb - 1) // nb
    # RDIAG(nt) + RPANEL(nt(nt-1)/2) + TRSM(nt) + GEMM(nt(nt-1)/2)
    n_tasks = 2 * nt + nt * (nt - 1)

    def leg(residue_batch):
        from contextlib import ExitStack
        with ExitStack() as st:
            st.enter_context(
                _params.cmdline_override("stage_compile", "1"))
            st.enter_context(_params.cmdline_override(
                "stage_compile_exclude", "GEMM"))
            if not residue_batch:
                st.enter_context(_params.cmdline_override(
                    "stage_residue_batch", "0"))
            ctx = parsec_tpu.init(nb_cores=cores)
            try:
                if not any(d.device_type == "tpu" for d in ctx.devices):
                    return None
                # the per-task delta is tens of us: each timed rep
                # clocks `iters` back-to-back solves (pools pre-built
                # outside the clock, the chain-leg methodology)
                iters = int(os.environ.get("BENCH_STAGEC_RES_ITERS",
                                           "4"))
                best = Y = stats0 = None
                for rep in range(1 + max(2, reps)):  # rep 0: compile
                    batch = []
                    for _ in range(1 if rep == 0 else iters):
                        L = TwoDimBlockCyclic(
                            n, n, nb, nb, dtype=np.float32
                            ).from_numpy(Lnp.copy())
                        B = TwoDimBlockCyclic(
                            n, nb, nb, nb, dtype=np.float32
                            ).from_numpy(B0.copy())
                        batch.append((B, dtrsm_lower_taskpool(L, B)))
                    stats0 = dict(ctx.stage_stats)
                    t0 = time.perf_counter()
                    for B, tp_ in batch:
                        ctx.add_taskpool(tp_)
                        ctx.wait()
                        pend = [B.data_of(*co).newest_copy().payload
                                for co in B.tiles()]
                        jax.block_until_ready([p for p in pend
                                     if hasattr(p, "block_until_ready")])
                    dt = (time.perf_counter() - t0) / len(batch)
                    if rep > 0:
                        best = dt if best is None else min(best, dt)
                    Y = batch[-1][0].to_numpy()
                delta = {k: (ctx.stage_stats[k] - stats0[k])
                         // len(batch) for k in ctx.stage_stats}
                return best, Y, delta
            finally:
                ctx.fini()

    out = {"residue_n": n, "residue_nb": nb, "residue_tasks": n_tasks}
    off = leg(False)
    on = leg(True)
    if off is None or on is None:
        out["residue_error"] = "no XLA device attached"
        return out
    (t_off, Y_off, s_off), (t_on, Y_on, s_on) = off, on
    out["residue_sched_off_us_per_task"] = round(t_off / n_tasks * 1e6, 1)
    out["residue_sched_on_us_per_task"] = round(t_on / n_tasks * 1e6, 1)
    out["residue_speedup"] = round(t_off / t_on, 2)
    out["residue_batches"] = s_on["residue_batches"]
    out["residue_batch_tasks"] = s_on["residue_batch_tasks"]
    out["residue_batches_off_leg"] = s_off["residue_batches"]
    out["residue_bit_exact_on_vs_off"] = bool(np.array_equal(Y_on, Y_off))
    return out


_STAGEC_DRIVER = r"""
import json, os, sys
sys.path.insert(0, os.environ["BENCH_REPO"])
import bench

print(json.dumps(bench.bench_stagec_inner(
    n=int(os.environ.get("BENCH_STAGEC_N", "768")),
    nb=int(os.environ.get("BENCH_STAGEC_NB", "64")),
    reps=int(os.environ.get("BENCH_REPS", "3")))))
"""


def bench_stagec_xrank_inner(n=192, nb=32, delay_ms=2, reps=2) -> dict:
    """BENCH_MODE=stagec cross-rank leg (ISSUE 20): the SAME 2-rank
    classic-runtime dpotrf over REAL loopback TCP sockets on a
    throttled link (every data message pays an injected ``delay_ms``
    sleep), stage-compiled with the ACTIVATION path (a cross-rank
    dependency edge serializes the boundary tile onto the wire) vs
    with CROSS-RANK LOWERING ON (``stage_compile_xrank``: every
    spanning wave compiles into ONE shard_map program whose inter-rank
    edges are an in-program all-gather; the wire carries control
    only).  Reported: µs/task per leg, per-rank host wire bytes (TCP
    serializes every shipped payload, so the byte drop is the proof
    the collective replaced the wire), the xstage engagement gauges,
    and bit-exactness of BOTH legs against an interpreted reference —
    the cross-rank program must reproduce the serialized schedule's
    floats exactly."""
    import concurrent.futures as cf
    from contextlib import ExitStack

    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.comm.tcp import TCPCommEngine, free_ports
    from parsec_tpu.ops import dpotrf_taskpool, make_spd
    from parsec_tpu.utils.params import params as _params

    ranks = 2
    M = make_spd(n)
    ntasks = _dpotrf_task_count((n + nb - 1) // nb)

    def run_once(stagec, xrank):
        with ExitStack() as ov:
            # overrides wrap ENGINE construction: the xs token rides
            # the HELLO, so the knob must be set before the dial
            ov.enter_context(_params.cmdline_override(
                "comm_mesh_local", "0"))   # payloads must ride the wire
            ov.enter_context(_params.cmdline_override(
                "ft_inject", f"delay:pct=100:ms={delay_ms}"))
            if stagec:
                ov.enter_context(
                    _params.cmdline_override("stage_compile", "1"))
            if xrank:
                ov.enter_context(_params.cmdline_override(
                    "stage_compile_xrank", "1"))
            eps = [("127.0.0.1", p) for p in free_ports(ranks)]
            with cf.ThreadPoolExecutor(ranks) as ex:
                engines = list(ex.map(
                    lambda r: TCPCommEngine(r, eps), range(ranks)))

            def rank_fn(rank):
                eng = RemoteDepEngine(engines[rank])
                ctx = parsec_tpu.Context(nb_cores=2, comm=eng)
                try:
                    A = TwoDimBlockCyclic(
                        n, n, nb, nb, P=ranks, Q=1, nodes=ranks,
                        rank=rank, dtype=np.float64
                        ).from_numpy(M.copy())
                    A.name = "descA"
                    tp = dpotrf_taskpool(A, rank=rank, nb_ranks=ranks)
                    t0 = time.perf_counter()
                    ctx.add_taskpool(tp)
                    ctx.wait()
                    wall = time.perf_counter() - t0
                    owned = {c: np.asarray(
                        A.data_of(*c).sync_to_host().payload)
                        for c in A.tiles() if A.rank_of(*c) == rank}
                    return (owned, wall, dict(ctx.stage_stats),
                            engines[rank].fabric.bytes_count)
                finally:
                    ctx.fini()

            with cf.ThreadPoolExecutor(ranks) as ex:
                results = list(ex.map(rank_fn, range(ranks)))
        L = np.zeros((n, n))
        stats, wire = [], []
        wall = 0.0
        for owned, w, st_, bts in results:
            wall = max(wall, w)
            stats.append(st_)
            wire.append(bts)
            for (m, k), t in owned.items():
                L[m * nb:m * nb + t.shape[0],
                  k * nb:k * nb + t.shape[1]] = t
        return np.tril(L), wall, stats, wire

    def leg(stagec, xrank):
        best = None
        for _ in range(max(1, reps)):   # rep 1 pays the compiles
            r = run_once(stagec, xrank)
            best = r if best is None or r[1] < best[1] else best
        return best

    L0, _w0, _s0, _b0 = leg(False, False)
    La, wa, sa, ba = leg(True, False)
    Lx, wx, sx, bx = leg(True, True)
    out = {
        "stagec_xrank_n": n, "stagec_xrank_nb": nb,
        "stagec_xrank_ranks": ranks, "stagec_xrank_tasks": ntasks,
        "stagec_xrank_link_delay_ms": delay_ms,
        "stagec_xrank_act_us_per_task": round(wa / ntasks * 1e6, 1),
        "stagec_xrank_us_per_task": round(wx / ntasks * 1e6, 1),
        "stagec_xrank_speedup_vs_act": round(wa / wx, 2),
        "stagec_xrank_wire_bytes_act": ba,
        "stagec_xrank_wire_bytes": bx,
        "stagec_xrank_wire_bytes_saved_frac": round(
            1.0 - sum(bx) / max(1, sum(ba)), 3),
        "stagec_xrank_xstage_tasks": sum(
            s["xstage_tasks"] for s in sx),
        "stagec_xrank_xstage_compiles": sum(
            s["xstage_compiles"] for s in sx),
        "stagec_xrank_xstage_fallbacks": sum(
            s["xstage_fallbacks"] for s in sx),
        "stagec_xrank_collective_bytes": sum(
            s["xstage_collective_bytes"] for s in sx),
        "stagec_xrank_act_xstage_tasks": sum(
            s["xstage_tasks"] for s in sa),
        "stagec_xrank_bit_exact_act_vs_interpreted": bool(
            np.array_equal(La, L0)),
        "stagec_xrank_bit_exact_vs_interpreted": bool(
            np.array_equal(Lx, L0)),
    }
    return out


_STAGEC_XRANK_DRIVER = r"""
import json, os, sys
sys.path.insert(0, os.environ["BENCH_REPO"])
import bench

print(json.dumps(bench.bench_stagec_xrank_inner(
    n=int(os.environ.get("BENCH_STAGEC_XRANK_N", "192")),
    nb=int(os.environ.get("BENCH_STAGEC_XRANK_NB", "32")),
    delay_ms=int(os.environ.get("BENCH_STAGEC_XRANK_DELAY_MS", "2")))))
"""


def bench_stagec_xrank(n=192, nb=32, delay_ms=2) -> dict:
    """The cross-rank stagec leg in its OWN scrubbed CPU subprocess:
    it needs a 4-device host mesh (2 ranks x 2 lanes for the shard_map
    program) which must not leak into the single-device dispatch
    measurement the main stagec subprocess makes."""
    import subprocess
    import sys as _sys

    env = _scrubbed_bench_env(
        n_devices=4,
        BENCH_STAGEC_XRANK_N=n, BENCH_STAGEC_XRANK_NB=nb,
        BENCH_STAGEC_XRANK_DELAY_MS=delay_ms)
    try:
        p = subprocess.run([_sys.executable, "-c",
                            _STAGEC_XRANK_DRIVER],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        if p.returncode != 0:
            return {"stagec_xrank_error":
                    p.stdout[-200:] + p.stderr[-200:]}
        return json.loads(p.stdout.strip().splitlines()[-1])
    except Exception as exc:  # noqa: BLE001
        return {"stagec_xrank_error": repr(exc)[:200]}


def bench_stagec(n=768, nb=64, reps=3) -> dict:
    """BENCH_MODE=stagec: the compiled-stage vs interpreted runtime
    comparison in a scrubbed CPU subprocess (bench_mesh pattern — the
    ratio is a host-dispatch measurement and must not depend on the
    parent's chip).  The cross-rank leg
    (ISSUE 20) rides the same record from its own subprocess;
    BENCH_STAGEC_XRANK=0 skips it."""
    import subprocess
    import sys as _sys

    env = _scrubbed_bench_env(
        BENCH_STAGEC_N=n, BENCH_STAGEC_NB=nb, BENCH_REPS=reps)
    try:
        p = subprocess.run([_sys.executable, "-c", _STAGEC_DRIVER],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        if p.returncode != 0:
            rec = {"stagec_error": p.stdout[-200:] + p.stderr[-200:]}
        else:
            rec = json.loads(p.stdout.strip().splitlines()[-1])
    except Exception as exc:  # noqa: BLE001
        rec = {"stagec_error": repr(exc)[:200]}
    if os.environ.get("BENCH_STAGEC_XRANK", "1") != "0":
        rec.update(bench_stagec_xrank(
            n=int(os.environ.get("BENCH_STAGEC_XRANK_N", "192")),
            nb=int(os.environ.get("BENCH_STAGEC_XRANK_NB", "32")),
            delay_ms=int(os.environ.get(
                "BENCH_STAGEC_XRANK_DELAY_MS", "2"))))
    return rec


def dgeqrf_flops(n: int, m: int = None) -> float:
    """LAPACK dgeqrf flop model (2mn^2 - 2n^3/3 for m >= n)."""
    m = n if m is None else m
    return 2.0 * m * n * n - 2.0 * n ** 3 / 3.0


def bench_geqrf(n=1024, nb=128, reps=3, cores=1, dtype=None):
    """BENCH_MODE=geqrf (ISSUE 12 satellite): the second workload —
    tile QR through the classic runtime — measured and residual-gated
    like dpotrf's runtime leg so it stops rotting silently.  Residual:
    ``||R^T R - A^T A|| / ||A^T A||`` (Q is discarded by design, so
    the normal-equations identity is the factor check)."""
    import jax
    import parsec_tpu
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.ops import dgeqrf_taskpool

    dtype = np.dtype(dtype or np.float32)
    rng = np.random.RandomState(7)
    M = rng.rand(n, n).astype(dtype)
    ctx = parsec_tpu.init(nb_cores=cores)
    try:
        best = None
        A = None
        for _ in range(max(2, reps)):
            A = TwoDimBlockCyclic(n, n, nb, nb, dtype=dtype
                                  ).from_numpy(M.copy())
            t0 = time.perf_counter()
            ctx.add_taskpool(dgeqrf_taskpool(A))
            ctx.wait()
            pend = [A.data_of(*co).newest_copy().payload
                    for co in A.tiles()]
            jax.block_until_ready([p for p in pend
                         if hasattr(p, "block_until_ready")])
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        R = np.triu(A.to_numpy()).astype(np.float64)
        G = M.astype(np.float64).T @ M.astype(np.float64)
        err = float(np.abs(R.T @ R - G).max() / np.abs(G).max())
        return best, err, {"geqrf_n": n, "geqrf_nb": nb,
                           "geqrf_residual": err}
    finally:
        ctx.fini()


def main() -> None:
    n = int(os.environ.get("BENCH_N", "8192"))
    nb = int(os.environ.get("BENCH_NB", "2048"))
    reps = int(os.environ.get("BENCH_REPS", "3"))
    cores = int(os.environ.get("BENCH_CORES", "1"))
    mode = os.environ.get("BENCH_MODE", "all")
    dtype = np.dtype(os.environ.get("BENCH_DTYPE", "float32"))

    if mode == "comm":
        extras = bench_comm()
        emit_json({
            "metric": "comm_small_am_msgs_per_s(loopback_tcp,coalesced)",
            "metric_id": "comm_small_am_msgs_per_s", "mode": mode,
            "value": extras["comm_tcp_small_msgs_per_s"],
            "unit": "msgs/s", "extras": extras})
        return
    if mode == "ft":
        extras = bench_ft(reps=reps)
        emit_json({
            "metric": "ft_detection_latency_ms(loopback_tcp,hb_10ms)",
            "metric_id": "ft_detection_latency_ms", "mode": mode,
            "value": extras["ft_detection_latency_ms"],
            "unit": "ms", "extras": extras})
        return
    if mode == "linkchaos":
        extras = bench_linkchaos(reps=reps)
        emit_json({
            "metric": "linkchaos_reconnect_ms(loopback_tcp,flap+replay)",
            "metric_id": "linkchaos_reconnect_ms", "mode": mode,
            "value": extras["linkchaos_reconnect_ms"],
            "unit": "ms", "extras": extras})
        return
    if mode == "elastic":
        extras = bench_elastic(reps=reps)
        emit_json({
            "metric": "elastic_shrink_recovery_s(3-rank_dpotrf,kill)",
            "metric_id": "elastic_shrink_recovery_s", "mode": mode,
            "value": extras["elastic_shrink_recovery_s"],
            "unit": "s", "extras": extras})
        return
    if mode == "mesh":
        extras = bench_mesh(
            burst=int(os.environ.get("BENCH_MESH_BURST", "64")),
            nb=int(os.environ.get("BENCH_MESH_NB", "96")),
            reps=reps,
            shape=os.environ.get("BENCH_MESH_SHAPE", "2x2"))
        emit_json({
            "metric": "mesh_wall_us_per_task(sharded,2x2,64-burst)",
            "metric_id": "mesh_wall_us_per_task", "mode": mode,
            "value": extras.get("mesh_wall_us_per_task", -1.0),
            "unit": "us/task", "extras": extras})
        return
    if mode == "overlap":
        extras = bench_overlap(
            n=int(os.environ.get("BENCH_OVERLAP_N", "768")),
            nb=int(os.environ.get("BENCH_OVERLAP_NB", "64")),
            ranks=int(os.environ.get("BENCH_OVERLAP_RANKS", "2")),
            delay_ms=int(os.environ.get("BENCH_OVERLAP_DELAY_MS", "8")))
        emit_json({
            "metric": "overlap_fraction_gain(throttled_link,on_vs_off)",
            "metric_id": "overlap_fraction_gain", "mode": mode,
            "value": extras.get("overlap_gain", -1.0),
            "unit": "fraction", "extras": extras})
        return
    if mode == "qwire":
        extras = bench_qwire(
            n=int(os.environ.get("BENCH_QWIRE_N", "256")),
            nb=int(os.environ.get("BENCH_QWIRE_NB", "64")),
            delay_ms=int(os.environ.get("BENCH_QWIRE_DELAY_MS", "2")))
        emit_json({
            "metric": "qwire_int8_bytes_vs_lossless(throttled_tcp_dpotrf)",
            "metric_id": "qwire_int8_bytes_vs_lossless", "mode": mode,
            "value": extras.get("int8_bytes_vs_lossless", -1.0),
            "unit": "fraction", "extras": extras})
        return
    if mode == "trace":
        extras = bench_trace(
            n=int(os.environ.get("BENCH_TRACE_N", "256")),
            nb=int(os.environ.get("BENCH_TRACE_NB", "64")),
            delay_ms=int(os.environ.get("BENCH_TRACE_DELAY_MS", "3")))
        emit_json({
            "metric": "trace_us_per_task_delta(throttled_tcp_dpotrf,"
                      "obs_flow_on_vs_off)",
            "metric_id": "trace_us_per_task_delta", "mode": mode,
            "value": extras.get("trace_us_per_task_delta", -1.0),
            "unit": "us/task", "extras": extras})
        return
    if mode == "serve":
        extras = bench_serve()
        emit_json({
            "metric": "serve_weighted_p99_vs_fifo(2-tenant,"
                      "persistent_ctx)",
            "metric_id": "serve_weighted_p99_vs_fifo", "mode": mode,
            "value": extras.get("serve_weighted_p99_vs_fifo", -1.0),
            "unit": "x", "extras": extras})
        return
    if mode == "dplane":
        extras = bench_dplane(
            n=int(os.environ.get("BENCH_DPLANE_N", "64")),
            tile=int(os.environ.get("BENCH_DPLANE_TILE", "8")),
            ranks=int(os.environ.get("BENCH_DPLANE_RANKS", "4")))
        emit_json({
            "metric": "redist_planned_bytes_vs_storm(tcp_reshard)",
            "metric_id": "redist_planned_bytes_vs_storm", "mode": mode,
            "value": extras.get("planned_bytes_vs_storm", -1.0),
            "unit": "fraction", "extras": extras})
        return
    if mode == "health":
        extras = bench_health(
            n=int(os.environ.get("BENCH_HEALTH_N", "256")),
            nb=int(os.environ.get("BENCH_HEALTH_NB", "64")),
            delay_ms=int(os.environ.get("BENCH_HEALTH_DELAY_MS", "3")))
        emit_json({
            "metric": "health_us_per_task_delta(throttled_tcp_dpotrf,"
                      "obs_live_on_vs_off)",
            "metric_id": "health_us_per_task_delta", "mode": mode,
            "value": extras.get("health_us_per_task_delta", -1.0),
            "unit": "us/task", "extras": extras})
        return
    if mode == "autotune":
        extras = bench_autotune(
            n=int(os.environ.get("BENCH_AUTOTUNE_N", "1024")),
            nb=int(os.environ.get("BENCH_AUTOTUNE_NB", "128")),
            link_mbps=float(os.environ.get("BENCH_AUTOTUNE_LINK_MBPS",
                                           "1.0")))
        emit_json({
            "metric": "autotune_tuned_vs_best_static(throttled_tcp_"
                      "dpotrf,closed_loop)",
            "metric_id": "autotune_tuned_vs_best_static", "mode": mode,
            "value": extras.get("autotune_tuned_vs_best_static", -1.0),
            "unit": "x", "extras": extras})
        return
    if mode == "dispatch":
        extras = bench_dispatch(
            burst=int(os.environ.get("BENCH_DISPATCH_BURST", "64")),
            nb=int(os.environ.get("BENCH_DISPATCH_NB", "96")),
            reps=reps)
        emit_json({
            "metric": "device_dispatch_us_per_task(batched,64-burst)",
            "metric_id": "device_dispatch_us_per_task", "mode": mode,
            "value": extras.get("batched_dispatch_us_per_task", -1.0),
            "unit": "us/task", "extras": extras})
        return
    if mode == "stagec":
        extras = bench_stagec(
            n=int(os.environ.get("BENCH_STAGEC_N", "768")),
            nb=int(os.environ.get("BENCH_STAGEC_NB", "64")),
            reps=reps)
        emit_json({
            "metric": "stagec_gflops(runtime_dpotrf,compiled_stages)",
            "metric_id": "stagec_gflops", "mode": mode,
            "value": extras.get("stagec_gflops", -1.0),
            "unit": "GFLOP/s", "extras": extras})
        return
    # everything below times the chip
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py: BENCH_MODE={mode} times the chip, and "
            f"jax.default_backend() is {jax.default_backend()!r}: no "
            f"TPU, no number")
    if mode == "geqrf":
        best, err, extras = bench_geqrf(
            n=int(os.environ.get("BENCH_GEQRF_N", "1024")),
            nb=int(os.environ.get("BENCH_GEQRF_NB", "128")),
            reps=reps, cores=cores, dtype=dtype)
        gf = dgeqrf_flops(n=int(os.environ.get("BENCH_GEQRF_N", "1024"))
                          ) / best / 1e9
        emit_json({
            "metric": "dgeqrf_gflops(runtime)",
            "metric_id": "dgeqrf_gflops/runtime", "mode": mode,
            "value": round(gf, 2) if err < NUMERICS_TOL else 0.0,
            "unit": "GFLOP/s", "residual": err, "extras": extras})
        return
    if mode == "all":
        bench_all(n, nb, reps, cores, dtype)
        return
    if mode == "capture":
        best, err = bench_capture(n, nb, reps, dtype)
    elif mode == "chain":
        n = int(os.environ.get("BENCH_CHAIN_N", "32768"))
        nb = int(os.environ.get("BENCH_CHAIN_NB", "2048"))
        best, err = bench_capture_chain(
            n, nb, reps, dtype, int(os.environ.get("BENCH_CHAIN_K", "4")))
    elif mode == "wave":
        best, err = bench_wave(n, nb, reps, dtype)
    else:
        best, err = bench_runtime(n, nb, reps, cores, dtype)
    emit(n, nb, dtype, mode, best, err)


if __name__ == "__main__":
    main()
