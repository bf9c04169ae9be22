"""stagec/ — whole-stage DAG->XLA compilation (ISSUE 12).

Differential tests: a stage-compiled run must EQUAL the fully
interpreted runtime TO ROUNDING (the compiled program unrolls the same
per-task subgraphs, but a fused stage and per-task dispatch are XLA
programs of different shape — ``conftest.assert_ulp_close`` bounds the
difference and says why), the DTD burst path must reject into the
interpreted fallback untouched, an injected trace failure must
downgrade transparently and permanently ONLY for its stage, and with
``stage_compile`` unset nothing changes at all.
"""
import numpy as np
import pytest

import parsec_tpu
from conftest import assert_ulp_close, spmd
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.ops import dpotrf_taskpool, make_spd
from parsec_tpu.utils.params import params


def _clear_stage_cache():
    from parsec_tpu.devices.batching import _stage_cache
    _stage_cache.clear()


def _run_dpotrf(n, nb, stagec, dtype=np.float32, mesh=None,
                max_tasks=None, nb_cores=2):
    from contextlib import ExitStack
    M = make_spd(n).astype(dtype)
    with ExitStack() as st:
        if stagec:
            st.enter_context(params.cmdline_override("stage_compile", "1"))
        if mesh:
            st.enter_context(
                params.cmdline_override("device_mesh_shape", mesh))
        if max_tasks is not None:
            st.enter_context(params.cmdline_override(
                "stage_compile_max_tasks", str(max_tasks)))
        ctx = parsec_tpu.init(nb_cores=nb_cores)
        try:
            A = TwoDimBlockCyclic(n, n, nb, nb,
                                  dtype=dtype).from_numpy(M.copy())
            tp = dpotrf_taskpool(A)
            ctx.add_taskpool(tp)
            ctx.wait()
            return (np.tril(A.to_numpy()), dict(ctx.stage_stats),
                    tp._stagec, M)
        finally:
            ctx.fini()


@pytest.mark.parametrize("n,nb,dtype", [
    (128, 32, np.float32),     # uniform
    (100, 32, np.float32),     # ragged edge tiles
    (96, 32, np.float64),      # second dtype
    (128, 64, np.float32),     # second NB
])
def test_stagec_dpotrf_matches_interpreted(n, nb, dtype):
    """The acceptance contract: compiled stages produce, to rounding,
    the factor the interpreted per-task/batched dispatch produces, across
    NB and dtype, and the compiled path really engages."""
    L0, s0, sc0, M = _run_dpotrf(n, nb, stagec=False, dtype=dtype)
    L1, s1, sc1, _ = _run_dpotrf(n, nb, stagec=True, dtype=dtype)
    assert sc0 is None and s0["stage_tasks"] == 0
    assert sc1 is not None
    nt = (n + nb - 1) // nb
    n_tasks = nt + 2 * (nt * (nt - 1) // 2) + \
        (nt * (nt - 1) * (nt - 2) // 6)
    assert s1["stage_tasks"] == n_tasks, s1
    assert s1["stage_fallbacks"] == 0, s1
    assert_ulp_close(L1, L0)
    resid = np.abs(L1.astype(np.float64) @ L1.astype(np.float64).T
                   - M).max() / np.abs(M).max()
    assert resid < 1e-5, f"residual {resid:.2e}"


def test_stagec_off_is_inert():
    """stage_compile unset: no compiler attaches, no counter moves —
    the pre-stagec runtime bit for bit."""
    L, stats, sc, _ = _run_dpotrf(96, 32, stagec=False)
    assert sc is None
    assert all(v == 0 for v in stats.values()), stats


def test_stagec_aot_cache_hits_across_taskpools():
    """A fresh taskpool over the same (spec, NB, dtype) must hit the
    AOT stage cache: no second trace/compile (the DTD cache_token
    steady-state, for PTG stages)."""
    _clear_stage_cache()
    with params.cmdline_override("stage_compile", "1"):
        ctx = parsec_tpu.init(nb_cores=2)
        try:
            M = make_spd(128)
            for rep in range(2):
                A = TwoDimBlockCyclic(128, 32, 32, 32, dtype=np.float32)
                A = TwoDimBlockCyclic(128, 128, 32, 32,
                                      dtype=np.float32).from_numpy(M.copy())
                ctx.add_taskpool(dpotrf_taskpool(A))
                ctx.wait()
                if rep == 0:
                    compiles0 = ctx.stage_stats["stage_compiles"]
                    assert compiles0 > 0
            assert ctx.stage_stats["stage_compiles"] == compiles0, \
                ctx.stage_stats
            assert ctx.stage_stats["stage_dispatches"] == 2 * (
                ctx.stage_stats["stage_dispatches"] // 2)
        finally:
            ctx.fini()


def test_stagec_noop_readers_lower_as_forwarders():
    """dtrsm's FWD spec mixes device classes with no-op reader classes
    (RDIAG/RPANEL broadcast L tiles through ``pass`` cpu BODYs): the
    ISSUE 13 relaxation lowers the readers as pure dataflow, so the
    WHOLE pool compiles — same answer as fully interpreted, with
    STAGE_TASKS covering every task."""
    from parsec_tpu.ops import dtrsm_lower_taskpool

    n, nb, nrhs = 128, 32, 8
    M = make_spd(n)
    rng = np.random.RandomState(5)
    B0 = rng.rand(n, nrhs).astype(np.float32)
    Lnp = np.linalg.cholesky(M.astype(np.float64)).astype(np.float32)

    def run(stagec):
        from contextlib import ExitStack
        with ExitStack() as st:
            if stagec:
                st.enter_context(
                    params.cmdline_override("stage_compile", "1"))
            ctx = parsec_tpu.init(nb_cores=2)
            try:
                L = TwoDimBlockCyclic(n, n, nb, nb,
                                      dtype=np.float32).from_numpy(
                    np.tril(Lnp).copy())
                B = TwoDimBlockCyclic(n, nrhs, nb, nrhs,
                                      dtype=np.float32).from_numpy(
                    B0.copy())
                ctx.add_taskpool(dtrsm_lower_taskpool(L, B))
                ctx.wait()
                return B.to_numpy(), dict(ctx.stage_stats)
            finally:
                ctx.fini()

    Y0, s0 = run(False)
    Y1, s1 = run(True)
    assert_ulp_close(Y1, Y0)
    assert s1["stage_tasks"] > 0, s1
    from parsec_tpu.stagec import class_verdicts
    from parsec_tpu.ops.dtrsm import _factories
    verdicts = class_verdicts(_factories()[0].jdf)
    assert verdicts["RDIAG"].ok and verdicts["RDIAG"].note, verdicts
    assert verdicts["RPANEL"].ok
    assert verdicts["TRSM"].ok and verdicts["GEMM"].ok


# a dtrsm-fwd variant whose reader classes carry REAL host bodies (a
# host-side checksum) — they must stay residue (STG300 is NOT relaxed
# for bodies that do work), interleaving with the compiled stages
MIXED_FWD_JDF = """
descL [ type="collection" ]
descB [ type="collection" ]
MT [ type="int" ]
NT [ type="int" ]

RDIAG(k)

k = 0 .. MT-1

: descL( k, k )

READ T <- descL( k, k )
       -> T TRSM( k, 0 .. NT-1 )

BODY
{
    _chk = float(np.sum(np.asarray(T)))
}
END

TRSM(k, n)

k = 0 .. MT-1
n = 0 .. NT-1

: descB( k, n )

READ T <- T RDIAG( k )
RW   X <- (k == 0) ? descB( k, n ) : C GEMM( k-1, k, n )
       -> descB( k, n )
       -> B GEMM( k, k+1 .. MT-1, n )

BODY [type=tpu]
{
    X = ops.trsm_lower(T, X)
}
END

GEMM(k, m, n)

k = 0 .. MT-2
m = k+1 .. MT-1
n = 0 .. NT-1

: descB( m, n )

READ A <- descL( m, k )
READ B <- X TRSM( k, n )
RW   C <- (k == 0) ? descB( m, n ) : C GEMM( k-1, m, n )
       -> (m == k+1) ? X TRSM( m, n ) : C GEMM( k+1, m, n )

BODY [type=tpu]
{
    C = ops.gemm_nn_sub(C, A, B)
}
END
"""


def _run_mixed_fwd(stagec, n=128, nb=32, nrhs=8, residue_batch=True):
    from contextlib import ExitStack

    from parsec_tpu import ops as ops_module
    from parsec_tpu.dsl import ptg

    M = make_spd(n)
    rng = np.random.RandomState(5)
    B0 = rng.rand(n, nrhs).astype(np.float32)
    Lnp = np.tril(np.linalg.cholesky(
        M.astype(np.float64)).astype(np.float32))
    with ExitStack() as st:
        if stagec:
            st.enter_context(params.cmdline_override("stage_compile", "1"))
        if not residue_batch:
            st.enter_context(
                params.cmdline_override("stage_residue_batch", "0"))
        ctx = parsec_tpu.init(nb_cores=2)
        try:
            L = TwoDimBlockCyclic(n, n, nb, nb,
                                  dtype=np.float32).from_numpy(Lnp.copy())
            B = TwoDimBlockCyclic(n, nrhs, nb, nrhs,
                                  dtype=np.float32).from_numpy(B0.copy())
            tp = ptg.compile_jdf(MIXED_FWD_JDF, name="mixed_fwd").new(
                descL=L, descB=B, MT=B.mt, NT=B.nt)
            tp.global_env["ops"] = ops_module
            ctx.add_taskpool(tp)
            ctx.wait()
            return B.to_numpy(), dict(ctx.stage_stats)
        finally:
            ctx.fini()


def test_stagec_residue_interleaves_with_compiled_stages():
    """A pool mixing compilable device classes with REAL host bodies
    (MIXED_FWD: RDIAG does host-side work) runs the stages compiled
    and the residue interpreted — same answer as fully interpreted,
    with STAGE_TASKS covering only the compilable part."""
    from parsec_tpu.dsl.ptg.parser import parse_jdf
    from parsec_tpu.stagec import class_verdicts

    Y0, s0 = _run_mixed_fwd(False)
    Y1, s1 = _run_mixed_fwd(True)
    assert_ulp_close(Y1, Y0)
    assert s1["stage_tasks"] > 0, s1
    verdicts = class_verdicts(parse_jdf(MIXED_FWD_JDF, name="mixed_fwd"))
    assert not verdicts["RDIAG"].ok and verdicts["RDIAG"].code == "STG300"
    assert verdicts["TRSM"].ok and verdicts["GEMM"].ok


def test_stagec_dtd_burst_rejects_into_fallback():
    """DTD taskpools have no static spec to lower: with stage_compile
    ON a DTD burst must run exactly as before (the batched dispatch
    path) and no stage counter may move."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu import dtd
    from parsec_tpu.dsl.dtd import INOUT, INPUT

    kern = jax.jit(lambda c, a, b: c - jnp.dot(a, b.T))
    rng = np.random.RandomState(11)
    mats = [[rng.rand(16, 16).astype(np.float32) for _ in range(3)]
            for _ in range(8)]

    def run(stagec):
        from contextlib import ExitStack
        with ExitStack() as st:
            if stagec:
                st.enter_context(
                    params.cmdline_override("stage_compile", "1"))
            ctx = parsec_tpu.init(nb_cores=2)
            try:
                tp = dtd.taskpool_new()
                ctx.add_taskpool(tp)

                def body(es, task):
                    c, a, b = dtd.unpack_args(task)
                    c -= a @ b.T

                boot = tp.tile_of_array(np.zeros((16, 16), np.float32))
                tp.insert_task(body, (boot, INOUT), (boot, INPUT),
                               (boot, INPUT))
                tp.add_chore(body, "tpu", kern)
                tiles = [[tp.tile_of_array(m.copy()) for m in row]
                         for row in mats]
                for c, a, b in tiles:
                    tp.insert_task(body, (c, INOUT), (a, INPUT),
                                   (b, INPUT))
                tp.wait()
                outs = [np.asarray(row[0].data.sync_to_host().payload)
                        for row in tiles]
                return outs, dict(ctx.stage_stats)
            finally:
                ctx.fini()

    out0, s0 = run(False)
    out1, s1 = run(True)
    assert s1["stage_tasks"] == 0 and s1["stage_compiles"] == 0, s1
    for a, b in zip(out0, out1):
        np.testing.assert_array_equal(a, b)


def test_stagec_trace_failure_downgrades_one_stage(monkeypatch):
    """An injected lowering failure on ONE stage must (a) fall that
    stage back to the interpreted path transparently (same factor
    to rounding), (b) leave the OTHER stages compiled, and (c) be
    permanent only for that stage — a repeat taskpool re-downgrades
    from the cached verdict without re-tracing."""
    import parsec_tpu.stagec.runtime as srt

    _clear_stage_cache()
    real_build = srt.build_stage_fn
    calls = {"n": 0, "fail": 0}

    def failing_build(tp, stage, layout, codes):
        calls["n"] += 1
        if stage.index == 0:
            calls["fail"] += 1
            raise RuntimeError("injected stage-lowering failure")
        return real_build(tp, stage, layout, codes)

    monkeypatch.setattr(srt, "build_stage_fn", failing_build)
    # small max_tasks so the DAG splits into several stages
    L1, s1, _sc, M = _run_dpotrf(160, 32, stagec=True, max_tasks=6)
    assert calls["fail"] == 1
    assert s1["stage_fallbacks"] == 1, s1
    assert s1["stage_compiles"] >= 1, s1           # other stages compiled
    assert s1["stage_tasks"] > 0, s1
    L0, _s0, _sc0, _ = _run_dpotrf(160, 32, stagec=False)
    assert_ulp_close(L1, L0)

    # permanence, scoped to the stage: a fresh taskpool re-downgrades
    # instantly from the cached _FAILED verdict (no new build call for
    # stage 0) while other stages hit their cached callables
    before = dict(calls)
    L2, s2, _sc2, _ = _run_dpotrf(160, 32, stagec=True, max_tasks=6)
    assert calls["fail"] == before["fail"], calls
    assert s2["stage_fallbacks"] == 1, s2
    assert_ulp_close(L2, L0)


def test_stagec_cache_token_covers_donate_and_max_tasks():
    """Regression (ISSUE 13 satellite): the AOT stage-cache key must
    cover the donate mask AND stage_compile_max_tasks — flipping either
    knob between otherwise identical runs must trigger fresh
    compilation (a stale hit would dispatch a program built for the
    wrong donation/partition), at unchanged numerics."""
    _clear_stage_cache()
    L0, s0, _x, M = _run_dpotrf(128, 32, stagec=False)

    # pin donate-by-default (ISSUE 20c) OFF so the device_donate flip
    # below actually changes the donate mask
    with params.cmdline_override("stage_compile", "1"), \
            params.cmdline_override("stage_compile_donate", "0"):
        ctx = parsec_tpu.init(nb_cores=2)
        try:
            def one(donate=None, max_tasks=None):
                from contextlib import ExitStack
                with ExitStack() as st:
                    if donate:
                        st.enter_context(
                            params.cmdline_override("device_donate", "1"))
                    if max_tasks is not None:
                        st.enter_context(params.cmdline_override(
                            "stage_compile_max_tasks", str(max_tasks)))
                    A = TwoDimBlockCyclic(
                        128, 128, 32, 32,
                        dtype=np.float32).from_numpy(M.copy())
                    ctx.add_taskpool(dpotrf_taskpool(A))
                    ctx.wait()
                    return np.tril(A.to_numpy())

            base = one()
            c1 = ctx.stage_stats["stage_compiles"]
            assert c1 > 0
            # same knobs again: pure cache hit, no new compile
            again = one()
            assert ctx.stage_stats["stage_compiles"] == c1
            # donate flip: the mask is part of the key -> fresh compile
            don = one(donate=True)
            c2 = ctx.stage_stats["stage_compiles"]
            assert c2 > c1, "donate-mask change hit a stale stage"
            # max_tasks flip: the plan key changes -> fresh plan+compile
            split = one(max_tasks=6)
            c3 = ctx.stage_stats["stage_compiles"]
            assert c3 > c2, "max_tasks change hit a stale plan/stage"
            for got in (base, again, don, split):
                assert_ulp_close(got, L0)
        finally:
            ctx.fini()


def test_stagec_donate_downgrade_replays_clean(monkeypatch):
    """stage_compile + device_donate interaction (ISSUE 13 satellite):
    with donation ON, an injected lowering failure downgrades one
    stage MID-RUN — its buffered activations must replay into the
    dynamic path and the donated packed buffers of the OTHER (still
    compiled, donating) stages must retire clean: same factor to rounding, no
    async errors, exactly one fallback."""
    import parsec_tpu.stagec.runtime as srt

    _clear_stage_cache()
    real_build = srt.build_stage_fn
    calls = {"fail": 0}

    def failing_build(tp, stage, layout, codes):
        if stage.index == 1:
            calls["fail"] += 1
            raise RuntimeError("injected mid-run lowering failure")
        return real_build(tp, stage, layout, codes)

    monkeypatch.setattr(srt, "build_stage_fn", failing_build)
    M = make_spd(160)
    from contextlib import ExitStack
    with ExitStack() as st:
        st.enter_context(params.cmdline_override("stage_compile", "1"))
        st.enter_context(params.cmdline_override("device_donate", "1"))
        st.enter_context(
            params.cmdline_override("stage_compile_max_tasks", "6"))
        ctx = parsec_tpu.init(nb_cores=2)
        try:
            A = TwoDimBlockCyclic(160, 160, 32, 32,
                                  dtype=np.float32).from_numpy(M.copy())
            ctx.add_taskpool(dpotrf_taskpool(A))
            ctx.wait()
            L1 = np.tril(A.to_numpy())
            s1 = dict(ctx.stage_stats)
        finally:
            ctx.fini()
    assert calls["fail"] >= 1
    assert s1["stage_fallbacks"] == 1, s1
    assert s1["stage_compiles"] >= 1, s1
    _clear_stage_cache()
    L0, _s0, _sc, _ = _run_dpotrf(160, 32, stagec=False)
    assert_ulp_close(L1, L0)


def _run_dposv(stagec, chain=True, n=128, nb=32, nrhs=32):
    from contextlib import ExitStack

    from parsec_tpu.ops import dposv

    M = make_spd(n)
    rng = np.random.RandomState(7)
    B0 = rng.rand(n, nrhs).astype(np.float32)
    with ExitStack() as st:
        if stagec:
            st.enter_context(params.cmdline_override("stage_compile", "1"))
        if not chain:
            st.enter_context(
                params.cmdline_override("stage_compile_chain", "0"))
        ctx = parsec_tpu.init(nb_cores=2)
        try:
            A = TwoDimBlockCyclic(n, n, nb, nb,
                                  dtype=np.float32).from_numpy(M.copy())
            B = TwoDimBlockCyclic(n, nrhs, nb, nrhs,
                                  dtype=np.float32).from_numpy(B0.copy())
            dposv(ctx, A, B)
            rejects = (list(ctx._stage_chain.rejects)
                       if ctx._stage_chain is not None else None)
            return B.to_numpy(), dict(ctx.stage_stats), rejects
        finally:
            ctx.fini()


def test_stagec_chain_dposv_one_program():
    """Cross-pool chaining (ISSUE 13 tentpole): single-rank dposv's
    three pools fuse into ONE chained program — both boundaries link
    (CHAIN_LINKS == 2), exactly one stage dispatch runs all three
    pools, zero fallbacks/rejects, and the solution equals the
    fully interpreted composition to rounding."""
    X0, s0, _r = _run_dposv(False)
    Xc, sc, rejects = _run_dposv(True, chain=True)
    assert sc["chain_links"] == 2, sc
    assert sc["chain_fallbacks"] == 0, sc
    assert sc["stage_dispatches"] == 1, sc
    assert rejects == [], rejects
    assert_ulp_close(Xc, X0)
    # chain off: same numerics through three per-pool programs
    Xp, sp, _r2 = _run_dposv(True, chain=False)
    assert sp["chain_links"] == 0 and sp["stage_dispatches"] == 3, sp
    assert_ulp_close(Xp, X0)


def test_stagec_chain_host_failure_falls_back(monkeypatch):
    """A chained program that fails to lower must fall back to the
    host-only callable, and the rider pools — finding no stash — must
    dispatch their stages normally: same result to rounding, CHAIN_FALLBACKS
    counted, nothing hangs."""
    import parsec_tpu.stagec.runtime as srt

    _clear_stage_cache()

    def failing_chain_run(*a, **k):
        raise RuntimeError("injected chained-lowering failure")

    import parsec_tpu.stagec.chain as chain_mod
    monkeypatch.setattr(chain_mod, "build_chain_run", failing_chain_run)
    X0, _s0, _r = _run_dposv(False)
    Xc, sc, _rej = _run_dposv(True, chain=True)
    assert sc["chain_links"] == 0, sc
    assert sc["chain_fallbacks"] >= 1, sc
    assert sc["stage_dispatches"] == 3, sc     # every pool dispatched
    assert_ulp_close(Xc, X0)
    _clear_stage_cache()   # drop the cached injected failure


def test_stagec_chain_rejects_multirank_dataflow():
    """2-rank dposv: cross-rank dataflow is not fusable — the chain
    planner must REJECT the boundaries (reason recorded, no fallback
    counted) and the distributed composition must still equal the
    interpreted one to rounding."""
    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.ops import dposv

    n, nb, nr = 128, 32, 2
    M = make_spd(n)
    B0 = np.random.RandomState(9).rand(n, nb).astype(np.float32)

    def run(stagec):
        from contextlib import ExitStack

        def rank_fn(rank, fabric):
            with ExitStack() as st:
                if stagec:
                    st.enter_context(
                        params.cmdline_override("stage_compile", "1"))
                eng = RemoteDepEngine(fabric.engine(rank))
                ctx = parsec_tpu.Context(nb_cores=2, comm=eng)
                try:
                    A = TwoDimBlockCyclic(
                        n, n, nb, nb, P=nr, Q=1, nodes=nr, rank=rank,
                        dtype=np.float32).from_numpy(M.copy())
                    A.name = "descA"
                    B = TwoDimBlockCyclic(
                        n, nb, nb, nb, P=nr, Q=1, nodes=nr, rank=rank,
                        dtype=np.float32).from_numpy(B0.copy())
                    B.name = "descB"
                    dposv(ctx, A, B, rank=rank, nb_ranks=nr)
                    owned = {c: np.asarray(
                        B.data_of(*c).sync_to_host().payload)
                        for c in B.tiles() if B.rank_of(*c) == rank}
                    rejects = (list(ctx._stage_chain.rejects)
                               if ctx._stage_chain is not None else None)
                    return owned, dict(ctx.stage_stats), rejects
                finally:
                    ctx.fini()

        results, _f = spmd(nr, rank_fn, timeout=300)
        X = np.zeros((n, nb), np.float32)
        stats, rejects = [], []
        for owned, st_, rej in results:
            stats.append(st_)
            rejects.append(rej)
            for (m, k), t in owned.items():
                X[m * nb:m * nb + t.shape[0], :t.shape[1]] = t
        return X, stats, rejects

    X0, _s0, _r0 = run(False)
    X1, s1, r1 = run(True)
    for s, rej in zip(s1, r1):
        assert s["chain_links"] == 0, s
        assert s["chain_fallbacks"] == 0, s     # rejected, not failed
        assert rej, "no chain-rejection reason was recorded"
    assert_ulp_close(X1, X0)


def test_stagec_residue_schedule_batches_groups():
    """Compiled residue schedule (ISSUE 13 tentpole): with GEMM
    operator-excluded (STG306), its instances run as device residue
    between compiled stages — pre-planned per-(level, class) groups
    must dispatch as bursts (RESIDUE_BATCHES > 0) with the knob on and
    stay per-task with it off, equal to rounding either way."""
    from contextlib import ExitStack

    n, nb = 160, 32
    M = make_spd(n)
    L0, _s, _sc, _m = _run_dpotrf(n, nb, stagec=False)

    def leg(residue_batch):
        with ExitStack() as st:
            st.enter_context(
                params.cmdline_override("stage_compile", "1"))
            st.enter_context(params.cmdline_override(
                "stage_compile_exclude", "GEMM"))
            if not residue_batch:
                st.enter_context(params.cmdline_override(
                    "stage_residue_batch", "0"))
            ctx = parsec_tpu.init(nb_cores=2)
            try:
                A = TwoDimBlockCyclic(n, n, nb, nb,
                                      dtype=np.float32
                                      ).from_numpy(M.copy())
                ctx.add_taskpool(dpotrf_taskpool(A))
                ctx.wait()
                return np.tril(A.to_numpy()), dict(ctx.stage_stats)
            finally:
                ctx.fini()

    L_on, s_on = leg(True)
    L_off, s_off = leg(False)
    assert s_on["residue_batches"] > 0, s_on
    assert s_on["residue_batch_tasks"] >= 2 * s_on["residue_batches"]
    assert s_off["residue_batches"] == 0, s_off
    assert_ulp_close(L_on, L0)
    assert_ulp_close(L_off, L0)
    # the exclusion really is the STG306 verdict
    from parsec_tpu.dsl.ptg.parser import parse_jdf
    from parsec_tpu.ops.dpotrf import DPOTRF_L_JDF
    from parsec_tpu.stagec import class_verdicts
    with params.cmdline_override("stage_compile_exclude", "GEMM"):
        v = class_verdicts(parse_jdf(DPOTRF_L_JDF, name="dpotrf"))
    assert not v["GEMM"].ok and v["GEMM"].code == "STG306", v["GEMM"]
    assert v["POTRF"].ok


def test_stagec_prestage_issues_and_hits():
    """Prestage/execute overlap (ISSUE 13 tentpole): a stage-compiled
    run prestages its packed-buffer tiles (H2D under lowering /
    execution) and the spawn-time accounting sees them land —
    PRESTAGE_ISSUED and PRESTAGE_HITS both move."""
    _clear_stage_cache()
    _l, s1, _sc, _m = _run_dpotrf(128, 32, stagec=True)
    assert s1["prestage_issued"] > 0, s1
    assert s1["prestage_hits"] > 0, s1
    assert s1["prestage_hits"] <= s1["prestage_issued"], s1


def test_stagec_sharded_locals_as_traced_scalars():
    """The ISSUE 13 sharded relaxation: a wave-front class whose body
    READS a declared local (``A = A * (m + 2)``) still compiles
    through shard_map on a mesh rank — the locals ride an (n, L) int32
    traced argument — and equals the interpreted path to rounding."""
    from contextlib import ExitStack

    from parsec_tpu.dsl import ptg

    spec = """
descA [ type="collection" ]
NT [ type="int" ]

Gen(m)
m = 0 .. NT-1
: descA( m, 0 )
RW A <- descA( m, 0 )
     -> A Scale( m )
BODY [type=tpu]
{
    A = A + 1.0
}
END

Scale(m)
m = 0 .. NT-1
: descA( m, 0 )
RW A <- A Gen( m )
     -> descA( m, 0 )
BODY [type=tpu]
{
    A = A * (m + 2)
}
END
"""
    nb, nt = 8, 4
    A0 = np.random.RandomState(3).rand(nt * nb, nb).astype(np.float32)

    def run(stagec, mesh=None):
        with ExitStack() as st:
            if stagec:
                st.enter_context(
                    params.cmdline_override("stage_compile", "1"))
            if mesh:
                st.enter_context(
                    params.cmdline_override("device_mesh_shape", mesh))
            ctx = parsec_tpu.init(nb_cores=2)
            try:
                A = TwoDimBlockCyclic(nt * nb, nb, nb, nb,
                                      dtype=np.float32
                                      ).from_numpy(A0.copy())
                tp = ptg.compile_jdf(spec, name="scalewave").new(
                    descA=A, NT=nt)
                ctx.add_taskpool(tp)
                ctx.wait()
                return A.to_numpy(), dict(ctx.stage_stats)
            finally:
                ctx.fini()

    R0, _s0 = run(False)
    R1, s1 = run(True, mesh="2x2")
    assert s1["stage_sharded"] >= 1, s1    # the locals-reader sharded
    assert s1["stage_fallbacks"] == 0, s1
    assert_ulp_close(R1, R0)


def test_stagec_mesh_sharded_bit_exact():
    """On a mesh rank (device_mesh_shape) eligible wave-front stages
    compile through shard_map and span chips — still equal, to rounding, to the
    single-chip interpreted path (ISSUE 12 sharded variant)."""
    # NT=5: the k=0 SYRK wave has 4 members = the 2x2 chip count
    L0, s0, _x, M = _run_dpotrf(160, 32, stagec=False)
    L1, s1, _y, _ = _run_dpotrf(160, 32, stagec=True, mesh="2x2")
    assert s1["stage_tasks"] > 0, s1
    assert s1["stage_sharded"] >= 1, s1
    assert_ulp_close(L1, L0)


def test_stagec_multirank_engages_per_rank():
    """2-rank classic runtime over the in-process fabric: each rank
    compiles its local stages (STAGE_TASKS > 0 on every rank), the
    cross-rank activations ride the untouched protocol, and the
    distributed factor equals the interpreted run to rounding."""
    from parsec_tpu.comm import RemoteDepEngine

    n, nb, nr = 128, 32, 2
    M = make_spd(n)

    def run(stagec):
        from contextlib import ExitStack

        def rank_fn(rank, fabric):
            with ExitStack() as st:
                if stagec:
                    st.enter_context(
                        params.cmdline_override("stage_compile", "1"))
                eng = RemoteDepEngine(fabric.engine(rank))
                ctx = parsec_tpu.Context(nb_cores=2, comm=eng)
                try:
                    A = TwoDimBlockCyclic(
                        n, n, nb, nb, P=2, Q=1, nodes=nr, rank=rank,
                        dtype=np.float32).from_numpy(M.copy())
                    A.name = "descA"
                    tp = dpotrf_taskpool(A, rank=rank, nb_ranks=nr)
                    ctx.add_taskpool(tp)
                    ctx.wait()
                    owned = {c: np.asarray(
                        A.data_of(*c).sync_to_host().payload)
                        for c in A.tiles() if A.rank_of(*c) == rank}
                    return owned, dict(ctx.stage_stats)
                finally:
                    ctx.fini()

        results, _f = spmd(nr, rank_fn, timeout=300)
        L = np.zeros((n, n), np.float32)
        stats = []
        for owned, st_ in results:
            stats.append(st_)
            for (m, k), t in owned.items():
                L[m * nb:m * nb + t.shape[0],
                  k * nb:k * nb + t.shape[1]] = t
        return np.tril(L), stats

    L0, s0 = run(False)
    L1, s1 = run(True)
    assert all(s["stage_tasks"] > 0 for s in s1), s1
    assert_ulp_close(L1, L0)


def test_stagec_lowerability_verdicts():
    """class_verdicts reuses the analysis/ findings: this_task bodies
    come back BDY201, numpy bodies BDY202, host-only classes STG300,
    clean device specs fully compilable."""
    from parsec_tpu.dsl.ptg.parser import parse_jdf
    from parsec_tpu.ops.dpotrf import DPOTRF_L_JDF
    from parsec_tpu.stagec import class_verdicts, lower_report

    v = class_verdicts(parse_jdf(DPOTRF_L_JDF, name="dpotrf"))
    assert all(x.ok for x in v.values()), v

    mixed = """
descA [ type="collection" ]

Gen(k)
k = 0 .. 3
: descA( k, 0 )
RW A <- descA( k, 0 )
     -> A Peek( k )
     -> descA( k, 0 )
BODY [type=tpu]
{
    A = A + 1.0
}
END

Peek(k)
k = 0 .. 3
: descA( k, 0 )
READ A <- A Gen( k )
BODY [type=tpu]
{
    A = A * (1 if this_task is None else 1)
}
END
"""
    v = class_verdicts(parse_jdf(mixed, name="mixed"))
    assert v["Gen"].ok
    assert not v["Peek"].ok and v["Peek"].code == "BDY201", v["Peek"]
    report = "\n".join(lower_report(parse_jdf(mixed, name="mixed")))
    assert "Peek: fallback [BDY201]" in report
    assert "Gen: compilable" in report


def test_stagec_gauges_in_exposition():
    """The STAGE_COMPILES / STAGE_TASKS / STAGE_FALLBACKS /
    STAGE_COMPILE_US gauges (guide §9.1) surface live in the Prometheus
    exposition after a stage-compiled run."""
    from parsec_tpu.obs import parse_exposition

    _clear_stage_cache()   # a warm AOT cache would leave compiles at 0
    with params.cmdline_override("stage_compile", "1"):
        ctx = parsec_tpu.Context(nb_cores=2)
        try:
            M = make_spd(128)
            A = TwoDimBlockCyclic(128, 128, 32, 32,
                                  dtype=np.float32).from_numpy(M)
            ctx.add_taskpool(dpotrf_taskpool(A))
            ctx.wait()
            text = ctx.obs.render_prometheus(labels={"rank": "0"})
        finally:
            ctx.fini()
    samples = parse_exposition(text)
    vals = {n: v for (n, _l), v in samples.items()
            if n.startswith("parsec_stagec_")}
    assert vals.get("parsec_stagec_stage_tasks", 0) > 0, sorted(vals)
    assert vals.get("parsec_stagec_stage_compiles", 0) > 0, vals
    assert vals.get("parsec_stagec_stage_fallbacks", -1) == 0, vals
    assert vals.get("parsec_stagec_stage_compile_us", 0) > 0, vals
    # ISSUE 13 gauges ride the same registry
    assert vals.get("parsec_stagec_prestage_hits", -1) >= 0, vals
    assert vals.get("parsec_stagec_chain_links", -1) == 0, vals
    assert vals.get("parsec_stagec_chain_fallbacks", -1) == 0, vals
    assert vals.get("parsec_stagec_residue_batches", -1) == 0, vals


def test_stagec_lock_discipline_enforced():
    """stagec/runtime.py opts into the concurrency lint with a
    populated _GUARDED_BY map: the shipped module is clean, and an
    injected unguarded access IS caught (the map really governs — the
    ISSUE 9 injected-violation convention)."""
    import os

    from parsec_tpu.analysis import lock_check

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "parsec_tpu", "stagec", "runtime.py")
    clean = [f for f in lock_check.lint_file(path)
             if f.severity in ("error", "warn")]
    assert not clean, clean
    src = open(path).read()
    bad = src + (
        "\n\ndef _unguarded_poke(rec):\n"
        "    rec.remaining -= 1\n")
    findings = lock_check.lint_source(bad, filename="runtime.py")
    assert any(f.code == "LCK301" and "remaining" in f.message
               for f in findings), findings


def test_stagec_lint_lower_report_cli():
    """tools/parsec_lint.py --lower-report prints the per-class
    verdicts, the per-STAGE partition, and — for multi-spec files —
    the chain verdicts for shipped specs, and exits 0
    (informational)."""
    import importlib.util
    import io
    import os
    import sys
    from contextlib import redirect_stdout

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_parsec_lint_test", os.path.join(root, "tools", "parsec_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_parsec_lint_test"] = mod
    spec.loader.exec_module(mod)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = mod.main(["--lower-report",
                       os.path.join(root, "parsec_tpu", "ops",
                                    "dpotrf.py"), "-q"])
    out = buf.getvalue()
    assert rc == 0
    assert "POTRF: compilable" in out and "GEMM: compilable" in out
    # per-stage verdicts (ISSUE 13): the partition of a toy instance
    assert "stage#0:" in out, out
    assert "stage(s) covering" in out, out

    # a multi-spec file additionally gets chain verdicts: dtrsm's
    # FWD ; BWD is fully fusable (shared descL/descB, memory-fed
    # first stage)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = mod.main(["--lower-report",
                       os.path.join(root, "parsec_tpu", "ops",
                                    "dtrsm.py"), "-q"])
    out = buf.getvalue()
    assert rc == 0
    assert "chain FWD_JDF -> BWD_JDF: fusable" in out, out


def test_stagec_lint_lower_report_chain_rejection_reason():
    """--lower-report prints the chain-rejection REASON when two pools
    fail to fuse (ISSUE 13 satellite): a second spec whose first stage
    awaits task activations (its compilable class is fed by a
    host-bodied producer) cannot chain."""
    import importlib.util
    import io
    import os
    import sys
    from contextlib import redirect_stdout

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_parsec_lint_test2",
        os.path.join(root, "tools", "parsec_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_parsec_lint_test2"] = mod
    spec.loader.exec_module(mod)

    unfusable = '''
A_JDF = """
descA [ type="collection" ]

Gen(k)
k = 0 .. 3
: descA( k, 0 )
RW A <- descA( k, 0 )
     -> descA( k, 0 )
BODY [type=tpu]
{
    A = A + 1.0
}
END
"""

B_JDF = """
descA [ type="collection" ]

Host(k)
k = 0 .. 3
: descA( k, 0 )
RW A <- descA( k, 0 )
     -> A Use( k )
     -> descA( k, 0 )
BODY
{
    A[...] = np.asarray(A) * 2.0
}
END

Use(k)
k = 0 .. 3
: descA( k, 0 )
READ A <- A Host( k )
BODY [type=tpu]
{
    A = A * 1.0
}
END
"""
'''
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as fh:
        fh.write(unfusable)
        path = fh.name
    try:
        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = mod.main(["--lower-report", path, "-q"])
        out = buf.getvalue()
        assert rc == 0
        assert "chain A_JDF -> B_JDF: rejected" in out, out
        assert "awaits" in out and "activation" in out, out
    finally:
        os.unlink(path)


# ---------------------------------------------------------------------- #
# donate-by-default (ISSUE 20c)                                          #
# ---------------------------------------------------------------------- #

def test_stagec_donate_by_default_under_eviction_pressure():
    """ISSUE 20c differential: inside compiled stages donation is ON
    WITHOUT the ``device_donate`` opt-in.  Under a 4 KiB device budget
    with small stages the arena evicts mid-run — donated-then-evicted
    stage buffers — and the factor must equal the interpreted one to
    rounding on BOTH legs: a donated buffer that later served stale bytes would
    corrupt the donate-on leg only."""
    from contextlib import ExitStack

    _clear_stage_cache()
    L0, _s0, _x, M = _run_dpotrf(160, 32, stagec=False)

    def leg(donate_default):
        with ExitStack() as st:
            st.enter_context(params.cmdline_override("stage_compile", "1"))
            st.enter_context(
                params.cmdline_override("stage_compile_max_tasks", "4"))
            if not donate_default:
                st.enter_context(params.cmdline_override(
                    "stage_compile_donate", "0"))
            ctx = parsec_tpu.init(nb_cores=2)
            try:
                for d in ctx.devices:
                    if d.device_type == "tpu":
                        d.mem_budget = 4 * 1024
                A = TwoDimBlockCyclic(160, 160, 32, 32,
                                      dtype=np.float32
                                      ).from_numpy(M.copy())
                ctx.add_taskpool(dpotrf_taskpool(A))
                ctx.wait()
                ev = sum(d.stats["evictions"] for d in ctx.devices
                         if d.device_type == "tpu")
                return np.tril(A.to_numpy()), ev, dict(ctx.stage_stats)
            finally:
                ctx.fini()

    Lon, ev_on, s_on = leg(True)
    Loff, ev_off, s_off = leg(False)
    assert ev_on > 0 and ev_off > 0, (ev_on, ev_off)   # pressure was real
    assert s_on["stage_tasks"] > 0 and s_on["stage_fallbacks"] == 0, s_on
    assert_ulp_close(Lon, L0)
    assert_ulp_close(Loff, L0)


ALIASED_JDF = """
descA [ type="collection" ]
NT [ type="int" ]

Add(m)
m = 0 .. NT-1
: descA( m, 0 )
READ U <- descA( m, 0 )
RW   X <- descA( m, 0 )
       -> descA( m, 0 )
BODY [type=tpu]
{
    X = X + U
}
END
"""


def test_stagec_bdy204_alias_keeps_donation_suppressed():
    """The BDY204-predicted aliased case (two flows read the same
    tile) must keep donation OFF even under donate-by-default: the
    same device buffer sits at two argument slots, so donating either
    would hand XLA a buffer the other flow still reads.  Observable:
    the donate mask is part of the AOT stage-cache key, so flipping
    ``stage_compile_donate`` around the aliased class must be a pure
    cache HIT (the mask is empty on both legs) — while the clean
    dpotrf control recompiles on the same flip."""
    from contextlib import ExitStack

    from parsec_tpu.analysis.body_check import check_jdf_bodies
    from parsec_tpu.dsl import ptg
    from parsec_tpu.dsl.ptg.parser import parse_jdf

    assert any(f.code == "BDY204"
               for f in check_jdf_bodies(parse_jdf(ALIASED_JDF,
                                                   name="aliased")))
    _clear_stage_cache()
    nb, nt = 8, 4
    A0 = np.random.RandomState(3).rand(nt * nb, nb).astype(np.float32)
    factory = ptg.compile_jdf(ALIASED_JDF, name="aliased")
    M = make_spd(128)

    with params.cmdline_override("stage_compile", "1"):
        ctx = parsec_tpu.init(nb_cores=2)
        try:
            def aliased(donate_knob):
                with ExitStack() as st:
                    if donate_knob is not None:
                        st.enter_context(params.cmdline_override(
                            "stage_compile_donate", donate_knob))
                    A = TwoDimBlockCyclic(
                        nt * nb, nb, nb, nb,
                        dtype=np.float32).from_numpy(A0.copy())
                    ctx.add_taskpool(factory.new(descA=A, NT=nt))
                    ctx.wait()
                    return A.to_numpy()

            R1 = aliased(None)            # donate-by-default leg
            c1 = ctx.stage_stats["stage_compiles"]
            assert c1 > 0
            R2 = aliased("0")             # donation knob OFF
            assert ctx.stage_stats["stage_compiles"] == c1, (
                "BDY204 class recompiled on a donate flip — donation "
                "was not suppressed")
            np.testing.assert_array_equal(R1, A0 * 2)
            np.testing.assert_array_equal(R2, A0 * 2)

            # clean control: dpotrf's mask really flips with the knob
            def clean(donate_knob):
                with ExitStack() as st:
                    if donate_knob is not None:
                        st.enter_context(params.cmdline_override(
                            "stage_compile_donate", donate_knob))
                    A = TwoDimBlockCyclic(
                        128, 128, 32, 32,
                        dtype=np.float32).from_numpy(M.copy())
                    ctx.add_taskpool(dpotrf_taskpool(A))
                    ctx.wait()

            clean(None)
            c2 = ctx.stage_stats["stage_compiles"]
            clean("0")
            assert ctx.stage_stats["stage_compiles"] > c2, (
                "clean class did NOT recompile on the donate flip — "
                "the control is broken")
        finally:
            ctx.fini()


# ---------------------------------------------------------------------- #
# cross-rank SPMD stages (ISSUE 20): negotiation + knob gating           #
# ---------------------------------------------------------------------- #

def _run_xrank_tcp(n, nb, nr, M, stagec, xrank, xstage_ctor=None):
    """2-rank dpotrf over loopback TCP.  ``xstage_ctor`` overrides the
    per-rank engine constructor's ``xstage`` kwarg (None: follow the
    knob) — the "xs" token rides the HELLO, so the knobs wrap engine
    CONSTRUCTION."""
    import concurrent.futures as cf
    from contextlib import ExitStack

    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.comm.tcp import TCPCommEngine, free_ports

    with ExitStack() as ov:
        if stagec:
            ov.enter_context(
                params.cmdline_override("stage_compile", "1"))
        if xrank:
            ov.enter_context(
                params.cmdline_override("stage_compile_xrank", "1"))
        eps = [("127.0.0.1", p) for p in free_ports(nr)]
        with cf.ThreadPoolExecutor(nr) as ex:
            engines = list(ex.map(
                lambda r: TCPCommEngine(
                    r, eps,
                    **({} if xstage_ctor is None or xstage_ctor[r] is None
                       else {"xstage": xstage_ctor[r]})),
                range(nr)))
        xs_links = [[engines[r].xstage_to(p) for p in range(nr) if p != r]
                    for r in range(nr)]

        def rank_fn(rank):
            eng = RemoteDepEngine(engines[rank])
            ctx = parsec_tpu.Context(nb_cores=2, comm=eng)
            try:
                A = TwoDimBlockCyclic(
                    n, n, nb, nb, P=nr, Q=1, nodes=nr, rank=rank,
                    dtype=np.float64).from_numpy(M.copy())
                A.name = "descA"
                tp = dpotrf_taskpool(A, rank=rank, nb_ranks=nr)
                ctx.add_taskpool(tp)
                ctx.wait()
                owned = {c: np.asarray(
                    A.data_of(*c).sync_to_host().payload)
                    for c in A.tiles() if A.rank_of(*c) == rank}
                return owned, dict(ctx.stage_stats)
            finally:
                ctx.fini()

        with cf.ThreadPoolExecutor(nr) as ex:
            results = list(ex.map(rank_fn, range(nr)))
    L = np.zeros((n, n))
    stats = []
    for owned, st_ in results:
        stats.append(st_)
        for (m, k), t in owned.items():
            L[m * nb:m * nb + t.shape[0], k * nb:k * nb + t.shape[1]] = t
    return np.tril(L), stats, xs_links


def test_stagec_xrank_engages_and_is_bit_exact():
    """Both ranks knob-on over loopback TCP: the spanning waves lower
    into ONE shard_map program per wave (XSTAGE_TASKS > 0 on every
    rank, zero fallbacks) and the distributed factor equals, to rounding,
    the interpreted run — the in-program all-gather must reproduce the
    serialized schedule's floats exactly."""
    n, nb, nr = 128, 32, 2
    M = make_spd(n)
    L0, _s0, _l0 = _run_xrank_tcp(n, nb, nr, M, False, False)
    Lx, sx, links = _run_xrank_tcp(n, nb, nr, M, True, True)
    assert all(all(l) for l in links), links   # xs negotiated both ways
    assert all(s["xstage_tasks"] > 0 for s in sx), sx
    assert all(s["xstage_fallbacks"] == 0 for s in sx), sx
    assert_ulp_close(Lx, L0)


def test_stagec_xrank_mixed_version_negotiates_down():
    """Mixed-version leg: rank 1's engine predates "xs" (ctor
    ``xstage=False`` — what an old build's HELLO looks like) while
    BOTH ranks run with the knob on.  Rank 0 must negotiate DOWN on
    the link — a one-sided cross-rank program would hang the stage
    rendezvous — and every wave keeps today's activation path:
    per-rank compiled stages, zero XSTAGE engagement, bit-for-bit."""
    n, nb, nr = 128, 32, 2
    M = make_spd(n)
    L0, _s0, _l0 = _run_xrank_tcp(n, nb, nr, M, False, False)
    L1, s1, links = _run_xrank_tcp(n, nb, nr, M, True, True,
                                   xstage_ctor=[None, False])
    assert not any(links[0]), links    # rank 0 sees no "xs" on the link
    for s in s1:
        assert s["xstage_tasks"] == 0 and s["xstage_compiles"] == 0, s1
    assert all(s["stage_tasks"] > 0 for s in s1), s1
    assert_ulp_close(L1, L0)


def test_wire_capture_xstage_bit_identity():
    """The frame-level differential (tests/wire_capture.py, leg H):
    toward a peer that never advertised "xs", a sender with the
    capability set puts no digest or boundary control frame on the
    wire: its data frames are BIT-IDENTICAL to the knob-unset run."""
    from wire_capture import capture_identity

    out = capture_identity()
    assert out["trace_frames_captured"] > 0
    assert out["xstage_mixed_version_bit_identical"]


def test_stagec_xrank_knob_unset_keeps_activation_path():
    """Knob-unset inertness: with only ``stage_compile`` on, no engine
    advertises "xs" (the capability defaults from the
    ``stage_compile_xrank`` knob), no cross-rank program ever builds
    (all XSTAGE gauges stay zero), and the factor matches the
    interpreted run bit-for-bit — the feature is invisible until BOTH
    the knob and the peer agree."""
    n, nb, nr = 128, 32, 2
    M = make_spd(n)
    L0, _s0, _l0 = _run_xrank_tcp(n, nb, nr, M, False, False)
    L1, s1, links = _run_xrank_tcp(n, nb, nr, M, True, False)
    assert not any(any(l) for l in links), links
    for s in s1:
        assert s["xstage_tasks"] == 0, s1
        assert s["xstage_compiles"] == 0, s1
        assert s["xstage_collective_bytes"] == 0, s1
        assert s["xstage_fallbacks"] == 0, s1
    assert all(s["stage_tasks"] > 0 for s in s1), s1
    assert_ulp_close(L1, L0)
