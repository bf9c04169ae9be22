"""Tile Cholesky (the north-star workload) correctness tests."""
import numpy as np
import pytest

import parsec_tpu
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.ops import dpotrf, dpotrf_taskpool, make_spd


@pytest.mark.parametrize("n,nb", [(64, 64), (128, 32), (192, 64), (100, 32)])
def test_dpotrf_numerics(ctx, n, nb):
    """L L^T must reconstruct A, including partial edge tiles (100/32)."""
    M = make_spd(n)
    A = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)
    tp = dpotrf_taskpool(A)
    ctx.add_taskpool(tp)
    ctx.wait()
    assert tp.completed
    nt = A.nt
    assert tp.nb_local_tasks == nt + 2 * (nt * (nt - 1) // 2) + \
        (nt * (nt - 1) * (nt - 2) // 6)
    L = np.tril(A.to_numpy())
    np.testing.assert_allclose(L @ L.T, M, atol=5e-4)


def test_dpotrf_matches_numpy(ctx):
    M = make_spd(96)
    A = TwoDimBlockCyclic(96, 96, 32, 32, dtype=np.float32).from_numpy(M)
    tp = dpotrf_taskpool(A)
    ctx.add_taskpool(tp)
    ctx.wait()
    L = np.tril(A.to_numpy())
    Lref = np.linalg.cholesky(M.astype(np.float64))
    np.testing.assert_allclose(L, Lref, atol=5e-4)


def test_dpotrf_batched_dispatch_bit_exact():
    """The stacked (unroll-mode) batched device path must be BIT-EXACT
    vs per-task dispatch: each task's subgraph lowers identically, one
    dispatch or many (ISSUE 5 acceptance)."""
    import parsec_tpu
    from parsec_tpu.utils.params import params

    M = make_spd(192)

    def run(batch_max):
        with params.cmdline_override("device_batch_max", str(batch_max)), \
             params.cmdline_override("device_tpu_max", "1"):
            c = parsec_tpu.init(nb_cores=2)
            try:
                A = TwoDimBlockCyclic(192, 192, 32, 32,
                                      dtype=np.float32).from_numpy(M.copy())
                tp = dpotrf_taskpool(A)
                c.add_taskpool(tp)
                c.wait()
                devs = [d for d in c.devices if d.device_type == "tpu"]
                batches = sum(d.stats["batches"] for d in devs)
                return np.tril(A.to_numpy()), batches
            finally:
                c.fini()

    L_single, b0 = run(1)
    L_batched, b1 = run(16)
    assert b0 == 0 and b1 > 0, (b0, b1)
    np.testing.assert_array_equal(L_batched, L_single)
    np.testing.assert_allclose(L_batched @ L_batched.T, M, atol=5e-4)


def test_dpotrf_mesh_sharded_residual_gate():
    """Mesh-sharded batched dispatch (device_mesh_shape; ISSUE 6): the
    north-star workload over a 2x2 chip mesh must hold the same
    residual gate as the single-chip path AND match it bit-exactly
    (unroll mode lowers the identical per-example subgraphs, one chip
    or four)."""
    import parsec_tpu
    from parsec_tpu.utils.params import params

    M = make_spd(192)

    def run(shape):
        from contextlib import ExitStack
        with ExitStack() as stack:
            if shape:
                stack.enter_context(
                    params.cmdline_override("device_mesh_shape", shape))
            else:
                stack.enter_context(
                    params.cmdline_override("device_tpu_max", "1"))
            c = parsec_tpu.init(nb_cores=2)
            try:
                A = TwoDimBlockCyclic(192, 192, 32, 32,
                                      dtype=np.float32).from_numpy(M.copy())
                c.add_taskpool(dpotrf_taskpool(A))
                c.wait()
                dev = c.device_by_type("tpu")
                return np.tril(A.to_numpy()), dict(dev.stats)
            finally:
                c.fini()

    L_mesh, st = run("2x2")
    assert st["mesh_dispatches"] > 0, st
    resid = np.abs(L_mesh @ L_mesh.T - M).max() / np.abs(M).max()
    assert resid < 1e-5, f"mesh-sharded dpotrf residual {resid:.2e}"
    L_single, _ = run(None)
    np.testing.assert_array_equal(L_mesh, L_single)


def test_dpotrf_runs_on_device(ctx4):
    M = make_spd(128)
    A = TwoDimBlockCyclic(128, 128, 32, 32, dtype=np.float32).from_numpy(M)
    tp = dpotrf_taskpool(A)
    ctx4.add_taskpool(tp)
    ctx4.wait()
    devs = [d for d in ctx4.devices if d.device_type == "tpu"]
    assert sum(d.stats["tasks"] for d in devs) == tp.nb_local_tasks
    L = np.tril(A.to_numpy())
    np.testing.assert_allclose(L @ L.T, M, atol=5e-4)
