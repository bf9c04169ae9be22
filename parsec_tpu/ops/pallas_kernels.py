"""Pallas TPU kernels for the hot ops.

The reference keeps its hot math in hand-tuned native kernels (CUDA chores
generated per task class, ref: parsec/interfaces/ptg/ptg-compiler/jdf2c.c:6557;
the lone .cu kernel tests/dsl/dtd/dtd_test_new_tile_cuda_kernels.cu). The
TPU-native analog is Pallas: Mosaic kernels that tile onto MXU/VPU with
explicit VMEM residency. Five kernels live here; ``lu_strip_vmem``,
``lu_pass_vmem`` and ``lu_update_vmem`` are the ones a task body of the
runtime runs, the other two serve the transformer model and the
ring-attention layer and no task class:

- ``flash_attention``: blockwise online-softmax attention (fwd is a single
  Pallas kernel with grid (BH, q_blocks, k_blocks); m/l/acc live in VMEM
  scratch that persists across the sequential k dimension). Differentiable
  via custom_vjp; the backward recomputes blockwise with the same online
  softmax inside ``lax.scan`` (memory O(T·block), not O(T^2)).
- ``matmul``: blocked GEMM with a float32 VMEM accumulator across the
  sequential K grid dimension (the MXU-feeding pattern the dpotrf update
  kernels ride on).
- ``lu_strip_vmem``: one strip of LU's pivoted panel (``ops.linalg.
  _lu_panel``, task class PANEL of ``ops.dgetrf_1d``) held in VMEM for
  all its column steps.
- ``lu_pass_vmem``: what that strip changed in the rest of the panel,
  the panel walked ONCE and in place: the columns from the strip's lane
  tile to the right edge, blocks of rows from the one holding the
  strip's first row down; the rows that moved stored from VMEM, the
  product subtracted under the block row.
- ``lu_update_vmem``: what a panel changes in one block column right of
  it (``ops.linalg.getrf_1d_update``, task class UPDATE): the column
  walked ONCE, the rows the panel's pivots moved stored from VMEM, the
  product subtracted from the rows under the block row and from no
  other.
  ``ops.linalg._lu_strip_lowered``, ``_lu_pass_lowered`` and
  ``_lu_update_lowered`` pick the three by the platform a program is
  lowered for and by the shapes; they read no parameter, ``use_pallas``
  and ``_on_tpu`` below are not asked.

Off-TPU (the virtual-CPU test mesh) ``flash_attention`` and ``matmul``
run with ``interpret=True``, so tests validate the exact kernel code
path; the three LU kernels are not lowered there at all (XLA is) and
their tests pass ``interpret=True`` themselves.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _on_tpu() -> bool:
    """True when Mosaic-compiled kernels can actually run.

    The MCA param ``device_tpu_platform`` (the same knob the device module
    honors, parsec_tpu/devices/__init__.py) pins this for tests: the
    virtual-CPU mesh sets it to "cpu", where only interpret mode exists.
    """
    from ..utils.params import params
    plat = params.get_or("device_tpu_platform", "string", "")
    if plat:
        return plat == "tpu"
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not _on_tpu()


def use_pallas() -> bool:
    """Policy knob: MCA param ``device_tpu_use_pallas`` (default: on-TPU)."""
    from ..utils.params import params
    v = params.get_or("device_tpu_use_pallas", "string", "")
    if v:
        return str(v).strip().lower() in ("1", "true", "yes", "on")
    return _on_tpu()


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                      *, causal: bool, scale: float, block_q: int,
                      block_k: int, num_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip blocks entirely above the diagonal
    needed = (ki * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(needed)
    def _body():
        # inputs stay in their native dtype (bf16 rides the MXU natively);
        # only the accumulation is f32
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [bq, bk]
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_prev = m_scr[:, :1]                                 # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)            # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                        # [bq, 1]
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_k - 1)
    def _fin():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


def _flash_fwd_stats_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                            m_scr, l_scr, acc_scr, *, causal: bool,
                            scale: float, block_q: int, block_k: int,
                            num_k: int):
    """The fwd kernel, additionally exporting each row's softmax stats
    (running max m, denominator l) so callers can MERGE partial-attention
    results across key blocks held elsewhere — the building block of
    sequence-parallel flash (ring attention's per-step local compute)."""
    _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                      causal=causal, scale=scale, block_q=block_q,
                      block_k=block_k, num_k=num_k)

    @pl.when(pl.program_id(2) == num_k - 1)
    def _export():
        # raw stats (l may be 0 / m may be _NEG_INF for fully-masked
        # rows — the merge ignores them; only o is safe-normalized).
        # Outputs are lane-replicated [bq, 128] (the scratch layout):
        # Mosaic requires 8x128-tileable output blocks, so a (1, bq)
        # row-vector block cannot lower; callers slice lane 0.
        m_ref[0] = m_scr[:]
        l_ref[0] = l_scr[:]


def flash_attention_stats(q: Any, k: Any, v: Any, causal: bool = False,
                          scale: float | None = None, block_q: int = 512,
                          block_k: int = 512):
    """Flash attention over one key block-set, returning
    ``(o, m, l)``: o = softmax(qk^T)v normalized within THIS k/v set,
    m/l = per-row running max / denominator ([B, H, T] f32). Merge rule
    for combining two sets a, b:

        m = max(m_a, m_b);  w_x = exp(m_x - m) * l_x
        o = (o_a w_a + o_b w_b) / (w_a + w_b);  l = w_a + w_b
    """
    B, H, T, D = q.shape
    Tk = k.shape[2]
    if scale is None:
        scale = D ** -0.5
    if _interpret():
        from ..parallel.mesh import _vma_of
        if _vma_of(q):
            # interpret-mode pallas inside a VMA-checked shard_map trips
            # jax's varying-axes checks on the emulation's slice ops; the
            # CPU-mesh tests take the identical-math jnp path instead
            # (the kernel itself is covered by the non-shard_map tests)
            return _flash_stats_reference(q, k, v, causal, float(scale))
    bq = _pick_block(T, block_q)
    bk = _pick_block(Tk, block_k)
    BH = B * H
    q3 = q.reshape(BH, T, D)
    k3 = k.reshape(BH, Tk, D)
    v3 = v.reshape(BH, Tk, D)
    num_q = pl.cdiv(T, bq)
    num_k = pl.cdiv(Tk, bk)
    kernel = functools.partial(
        _flash_fwd_stats_kernel, causal=causal, scale=float(scale),
        block_q=bq, block_k=bk, num_k=num_k)
    o3, m3, l3 = pl.pallas_call(
        kernel,
        grid=(BH, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((BH, T, D), q3),
            _out_struct((BH, T, 128), q3, jnp.float32),
            _out_struct((BH, T, 128), q3, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(q3, k3, v3)
    return (o3.reshape(B, H, T, D), m3[..., 0].reshape(B, H, T),
            l3[..., 0].reshape(B, H, T))


def _flash_stats_reference(q, k, v, causal: bool, scale: float):
    """jnp twin of the stats kernel (same m/l conventions: local-index
    causal mask, raw l=0 / m=_NEG_INF on fully-masked rows, o safe-
    normalized)."""
    T, Tk = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.arange(T)[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: kernel leaves m=_NEG_INF, l=0 (exp(_NEG_INF -
    # _NEG_INF)=1 would otherwise pollute l)
    dead = m <= _NEG_INF
    l = jnp.where(dead, 0.0, p.sum(axis=-1))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v,
                   preferred_element_type=jnp.float32) / l_safe[..., None]
    o = jnp.where(dead[..., None], 0.0, o).astype(q.dtype)
    return o, m, l


def _flash_fwd(q3: Any, k3: Any, v3: Any, causal: bool, scale: float,
               block_q: int, block_k: int) -> Any:
    BH, T, D = q3.shape
    Tk = k3.shape[1]
    num_q = pl.cdiv(T, block_q)
    num_k = pl.cdiv(Tk, block_k)
    kernel = functools.partial(
        _flash_fwd_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, num_k=num_k)
    grid = (BH, num_q, num_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((BH, T, D), q3),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(q3, k3, v3)


def _out_struct(shape, like, dtype=None):
    """Output ShapeDtypeStruct in ``dtype`` (default: ``like``'s) carrying
    — inside a VMA-checked shard_map — ``like``'s varying-mesh-axes set
    (pallas_call cannot infer vma itself; without it check_vma=True
    rejects the call)."""
    from ..parallel.mesh import _vma_of
    dtype = like.dtype if dtype is None else dtype
    vma = _vma_of(like)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _pick_block(t: int, pref: int) -> int:
    b = min(pref, t)
    while t % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q3, k3, v3, causal, scale, block_q, block_k):
    return _flash_fwd(q3, k3, v3, causal, scale, block_q, block_k)


def _flash_vjp_fwd(q3, k3, v3, causal, scale, block_q, block_k):
    o = _flash_fwd(q3, k3, v3, causal, scale, block_q, block_k)
    return o, (q3, k3, v3)


def _flash_vjp_bwd(causal, scale, block_q, block_k, res, g):
    q3, k3, v3 = res
    # blockwise recompute in three scans over k blocks (stats, dv+delta,
    # dq/dk); no per-block tensor is ever stacked, so memory is O(T*block_k)
    BH, T, D = q3.shape
    Tk = k3.shape[1]
    bk = _pick_block(Tk, block_k)
    nk = Tk // bk
    qf = q3.astype(jnp.float32)
    kf = k3.reshape(BH, nk, bk, D).astype(jnp.float32)
    vf = v3.reshape(BH, nk, bk, D).astype(jnp.float32)
    gf = g.astype(jnp.float32)
    qpos = jnp.arange(T)

    def stats_step(carry, blk):
        m, l = carry
        kb, j = blk
        s = jnp.einsum("bqd,bkd->bqk", qf, kb,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = j * bk + jnp.arange(bk)
            s = jnp.where(qpos[:, None] >= kpos[None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        l = l * jnp.exp(m - m_new) + jnp.exp(s - m_new[..., None]).sum(-1)
        return (m_new, l), None

    def _like_q(x):
        # scan carries must share the inputs' varying-axes set under a
        # VMA-checked shard_map (match_vma exists for exactly this)
        from ..parallel.mesh import match_vma
        return match_vma(x, qf)

    (m, l), _ = jax.lax.scan(
        stats_step,
        (_like_q(jnp.full((BH, T), _NEG_INF, jnp.float32)),
         _like_q(jnp.zeros((BH, T), jnp.float32))),
        (kf.transpose(1, 0, 2, 3), jnp.arange(nk)))
    l = jnp.where(l == 0.0, 1.0, l)

    def _block_p_dp(kb, vb, j):
        """Recompute this k block's normalized probs and dP (never stacked
        across blocks — memory stays O(T*bk))."""
        s = jnp.einsum("bqd,bkd->bqk", qf, kb,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = j * bk + jnp.arange(bk)
            s = jnp.where(qpos[:, None] >= kpos[None, :], s, _NEG_INF)
        p = jnp.exp(s - m[..., None]) / l[..., None]          # [B,T,bk]
        dp = jnp.einsum("bqd,bkd->bqk", gf, vb,
                        preferred_element_type=jnp.float32)
        return p, dp

    # pass 2: dv per block (legitimately O(Tk) — it IS the gradient) and
    # delta = rowsum(dO * O), accumulated blockwise
    def delta_step(delta_acc, blk):
        kb, vb, j = blk
        p, dp = _block_p_dp(kb, vb, j)
        dv = jnp.einsum("bqk,bqd->bkd", p, gf,
                        preferred_element_type=jnp.float32)
        return delta_acc + jnp.einsum("bqk,bqk->bq", p, dp), dv

    kfT = kf.transpose(1, 0, 2, 3)
    vfT = vf.transpose(1, 0, 2, 3)
    delta, dvs = jax.lax.scan(
        delta_step, _like_q(jnp.zeros((BH, T), jnp.float32)),
        (kfT, vfT, jnp.arange(nk)))

    # pass 3: recompute p/dp per block for dq/dk
    def dq_step(dq, blk):
        kb, vb, j = blk
        p, dp = _block_p_dp(kb, vb, j)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, kb,
                             preferred_element_type=jnp.float32)
        return dq, jnp.einsum("bqk,bqd->bkd", ds, qf,
                              preferred_element_type=jnp.float32)

    dq, dks = jax.lax.scan(dq_step, _like_q(jnp.zeros_like(qf)),
                           (kfT, vfT, jnp.arange(nk)))
    dk = dks.transpose(1, 0, 2, 3).reshape(BH, Tk, D)
    dv = dvs.transpose(1, 0, 2, 3).reshape(BH, Tk, D)
    return dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q: Any, k: Any, v: Any, causal: bool = True,
                    scale: float | None = None, block_q: int = 512,
                    block_k: int = 512) -> Any:
    """Pallas flash attention. q,k,v: [B, H, T, Dh] -> [B, H, T, Dh]."""
    B, H, T, D = q.shape
    Tk = k.shape[2]
    if scale is None:
        scale = D ** -0.5
    bq = _pick_block(T, block_q)
    bk = _pick_block(Tk, block_k)
    q3 = q.reshape(B * H, T, D)
    k3 = k.reshape(B * H, Tk, D)
    v3 = v.reshape(B * H, Tk, D)
    o = _flash(q3, k3, v3, causal, float(scale), bq, bk)
    return o.reshape(B, H, T, D)


# ---------------------------------------------------------------------------
# Blocked GEMM
# ---------------------------------------------------------------------------

def _matmul_kernel(a_ref, b_ref, o_ref, acc_scr, *, num_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    acc_scr[:] += jax.lax.dot_general(
        a_ref[:], b_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == num_k - 1)
    def _fin():
        o_ref[:] = acc_scr[:].astype(o_ref.dtype)


def matmul(a: Any, b: Any, block_m: int = 256, block_n: int = 256,
           block_k: int = 512) -> Any:
    """Blocked Pallas GEMM: [M, K] @ [K, N] with f32 VMEM accumulation.
    Differentiable: the VJP runs the same kernel on the transposes
    (dA = g @ B^T, dB = A^T @ g)."""
    return _matmul_vjp(a, b, block_m, block_n, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _matmul_vjp(a, b, block_m, block_n, block_k):
    return _matmul_impl(a, b, block_m, block_n, block_k)


def _matmul_vjp_fwd(a, b, block_m, block_n, block_k):
    return _matmul_impl(a, b, block_m, block_n, block_k), (a, b)


def _matmul_vjp_bwd(block_m, block_n, block_k, res, g):
    a, b = res
    da = _matmul_impl(g, b.T, block_m, block_n, block_k)
    db = _matmul_impl(a.T, g, block_m, block_n, block_k)
    return da.astype(a.dtype), db.astype(b.dtype)


_matmul_vjp.defvjp(_matmul_vjp_fwd, _matmul_vjp_bwd)


def _matmul_impl(a: Any, b: Any, block_m: int, block_n: int,
                 block_k: int) -> Any:
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm = _pick_block(M, block_m)
    bn = _pick_block(N, block_n)
    bk = _pick_block(K, block_k)
    num_k = K // bk
    kernel = functools.partial(_matmul_kernel, num_k=num_k)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, num_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(a, b)


# ---------------------------------------------------------------------------
# One strip of LU's pivoted panel, held in VMEM for all its column steps
# ---------------------------------------------------------------------------

#: what a strip kernel may ask of a core's VMEM: working sets above this
#: take the XLA loop (the smallest VMEM of the chips in use is 64 MiB)
_LU_STRIP_VMEM_MAX = 48 << 20


def _lu_strip_vmem_bytes(w: int, n: int) -> int:
    """The VMEM one strip kernel asks for: the strip as it comes in and
    as it goes out, the gather, and room for Mosaic's own scratch."""
    return 2 * w * n * 4 + n * 4 + (4 << 20)


def lu_strip_fits(w: int, n: int) -> bool:
    """The shape rule of :func:`lu_strip_vmem`: the N rows fill whole
    lanes and the working set fits the VMEM the kernel asks for."""
    return n % 128 == 0 and _lu_strip_vmem_bytes(w, n) <= _LU_STRIP_VMEM_MAX


def _lu_strip_kernel(d0_ref, st_ref, out_ref, g_ref, piv_ref,
                     *, w: int, rows: int, ch: int):
    # row position r*128 + l of the strip's (rows, 128) view of a column
    pos = (jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 0) * 128
           + jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1))
    cpos = (jax.lax.broadcasted_iota(jnp.int32, (1, ch, 128), 1) * 128
            + jax.lax.broadcasted_iota(jnp.int32, (1, ch, 128), 2))  # in a chunk
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 128), 2)
    lane2 = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (w, 1, 1), 0)
    n, d0 = rows * 128, d0_ref[0]
    out_ref[...] = st_ref[...]
    g_ref[...] = pos

    def exchange(ref, at_d, at_p, is_d, is_p, lo):
        # entries d and p of every column (a max over one lane keeps the
        # bits, the sign of a zero too); p may be d: read p again
        sd = ref[at_d]
        xd = jnp.max(jnp.where(is_d, sd, lo), axis=-1, keepdims=True)
        xp = jnp.max(jnp.where(is_p, ref[at_p], lo), axis=-1, keepdims=True)
        ref[at_d] = jnp.where(is_d, xp, sd)
        ref[at_p] = jnp.where(is_p, xd, ref[at_p])
        return xp

    def step(i, carry):
        d = d0 + i
        a = jnp.where(pos >= d, jnp.abs(out_ref[pl.ds(i, 1)][0]), -1.0)
        p = jnp.min(jnp.where(a == jnp.max(a), pos, n))   # first on a tie
        rd, ld, rp, lp = d // 128, d % 128, p // 128, p % 128
        xp = exchange(out_ref, (slice(None), pl.ds(rd, 1)),
                      (slice(None), pl.ds(rp, 1)), lane == ld, lane == lp,
                      -jnp.inf)                             # (w, 1, 1)
        exchange(g_ref, pl.ds(rd, 1), pl.ds(rp, 1), lane2 == ld, lane2 == lp,
                 -1)
        pivot = jnp.max(jnp.where(col == i, xp, -jnp.inf))
        right = jnp.where(col > i, xp, 0.0)                 # (w, 1, 1)

        def chunk(c, carry):
            r0 = pl.multiple_of(c * ch, ch)
            rs = pl.ds(r0, ch)
            x = out_ref[:, rs]
            mult = out_ref[pl.ds(i, 1), rs] / pivot
            out_ref[:, rs] = jnp.where(
                cpos + r0 * 128 > d,                        # the rows under d
                jnp.where(col == i, mult, x - right * mult), x)
            return carry

        # the chunks of rows wholly above d hold nothing active
        jax.lax.fori_loop(d // (128 * ch), rows // ch, chunk, 0)
        piv_ref[i] = p
        return carry

    jax.lax.fori_loop(0, w, step, 0)


def lu_strip_vmem(st: Any, d0: Any, *, interpret: bool = False) -> Any:
    """``ops.linalg._lu_strip`` as ONE Mosaic kernel: the (w, N) strip is
    brought into VMEM once, its w column steps run there, and the strip,
    the gather and the w pivot rows are written back once.

    The strip is viewed as (w, N/128, 128): a column is N/1024 dense
    vregs and row i sits at ``[i // 128, i % 128]``.  A step searches its
    column (two reductions to a scalar: the largest magnitude among the
    active rows, then the first row that has it), exchanges rows d and p
    through two one-sublane slices, and updates the strip in ONE pass
    that walks chunks of 8 sublanes from the one that holds row d down.
    The arithmetic is ``_lu_strip``'s to the operation: one divide a
    multiplier, a multiply then a subtract; the rows above ``d0`` are
    never written.  Shapes: :func:`lu_strip_fits`.  Reads no parameter;
    ``interpret`` is for the tests on the CPU."""
    w, n = st.shape
    rows = n // 128
    kernel = functools.partial(_lu_strip_kernel, w=w, rows=rows,
                               ch=math.gcd(rows, 8))
    out, g, piv = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((w, rows, 128), st.dtype),
                   jax.ShapeDtypeStruct((rows, 128), jnp.int32),
                   jax.ShapeDtypeStruct((w,), jnp.int32)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_lu_strip_vmem_bytes(w, n)),
        name="lu_strip_vmem", interpret=interpret,
    )(jnp.reshape(d0, (1,)).astype(jnp.int32), st.reshape(w, rows, 128))
    return out.reshape(w, n), g.reshape(n), piv


# ---------------------------------------------------------------------------
# One strip pass of LU's pivoted panel: the columns right of the strip,
# read once and written once, interchange and update together
# ---------------------------------------------------------------------------

#: columns of a lane tile.  A pass brings the strip's lane tile in whole
#: (the multipliers are read from it) and exchanges rows in ALL of it
#: but the strip's own columns, so what waits for the end of the panel
#: is whole lane tiles
LU_PASS_TILE = 128

#: rows of the panel one grid step of a pass holds in VMEM, and rows of
#: it one product takes
_LU_PASS_ROWS = 512
_LU_PASS_CHUNK = 128


def lu_pass_window(c0: int) -> int:
    """The first column a pass over the strip that starts at column
    ``c0`` brings in and exchanges rows in: its lane tile's."""
    return c0 // LU_PASS_TILE * LU_PASS_TILE


def _lu_pass_vmem_bytes(w: int, nb: int) -> int:
    """The VMEM one pass kernel asks for at its widest window: a block
    of rows coming in and one going out, each twice (the pipeline's two
    buffers), the moved rows likewise, a chunk's
    product and its operands, and room for Mosaic's own scratch."""
    return (4 * _LU_PASS_ROWS + 4 * 2 * w + 4 * _LU_PASS_CHUNK) * nb * 4 \
        + (4 << 20)


def lu_pass_fits(w: int, n: int, nb: int) -> bool:
    """The shape rule of :func:`lu_pass_vmem`: whole blocks of rows,
    whole lane tiles of columns, a strip that lies in one lane tile."""
    return (n % _LU_PASS_ROWS == 0 and nb % LU_PASS_TILE == 0
            and LU_PASS_TILE % w == 0 and w % 8 == 0
            and _lu_pass_vmem_bytes(w, nb) <= _LU_STRIP_VMEM_MAX)


def _lu_pass_kernel(d0_ref, rows_ref, x_ref, st_ref, new_ref, out_ref, *rest,
                    w: int, off: int):
    rows_blk, width = x_ref.shape
    tile, ch = LU_PASS_TILE, _LU_PASS_CHUNK
    chunks = rows_blk // ch
    *nxt_ref, wide_ref, head_ref, next_ref = rest   # no next strip after the last
    i = pl.program_id(0)
    d0 = d0_ref[0]
    first = d0 // rows_blk          # the block of rows that holds row d0

    @pl.when(i == first)
    def _():
        # the rows that moved, listed by the block of rows that holds
        # them, each list in the order of ``rows``: once a pass
        def clear(b, carry):
            head_ref[b] = -1
            return carry

        def push(k, carry):
            j = 2 * w - 1 - k
            b = rows_ref[j] // rows_blk
            next_ref[j] = head_ref[b]
            head_ref[b] = j
            return carry

        jax.lax.fori_loop(0, pl.num_programs(0), clear, 0)
        jax.lax.fori_loop(0, 2 * w, push, 0)

    @pl.when(i >= first)            # above it nothing moves: see the index maps
    def _():
        r0 = i * rows_blk
        col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        right = col >= off + w
        outside = right | (col < off)
        # the block is brought up to date where it came in, then walked
        # once.  The strip goes back into its columns: transposed on the
        # way, as w rows of a lane tile's worth (the other rows are
        # never read)
        wide_ref[off:off + w, :] = st_ref[...]
        strip = (col[:, :tile] >= off) & (col[:, :tile] < off + w)
        x_ref[:, :tile] = jnp.where(strip, wide_ref[...].T, x_ref[:, :tile])

        def put(j):
            # one moved row: the pivots' rows first, then the block row
            # (a pivot row inside the block row ends as its row of U)
            at = pl.ds(rows_ref[j] - r0, 1)
            x_ref[at, :] = jnp.where(outside, new_ref[pl.ds(j, 1), :],
                                     x_ref[at, :])
            return next_ref[j]

        jax.lax.while_loop(lambda j: j >= 0, put, head_ref[i])
        if not nxt_ref:             # the panel's last strip: nothing to update
            out_ref[...] = x_ref[...]
            return
        u = new_ref[w:, :]
        pos = jax.lax.broadcasted_iota(jnp.int32, (ch, 1), 0) + r0

        def keep(c, carry):
            rs = pl.ds(pl.multiple_of(c * ch, ch), ch)
            out_ref[rs, :] = x_ref[rs, :]
            return carry

        def update(c, carry):
            rs = pl.ds(pl.multiple_of(c * ch, ch), ch)
            t = x_ref[rs, :]
            prod = jnp.dot(t[:, off:off + w], u,
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
            out_ref[rs, :] = jnp.where((pos + c * ch >= d0 + w) & right,
                                       t - prod, t)
            return carry

        # the chunks wholly above the block row's last row hold no row to update
        under = jnp.minimum(jnp.maximum(d0 + w - r0, 0) // ch, chunks)
        jax.lax.fori_loop(0, under, keep, 0)
        jax.lax.fori_loop(under, chunks, update, 0)
        # the next strip comes out as the strip kernel takes it, transposed
        at = (off + w) // tile * tile
        nxt = (off + w) % tile
        nxt_ref[0][...] = out_ref[:, at:at + tile].T[nxt:nxt + w]


def lu_pass_vmem(x: Any, st: Any, rows: Any, new: Any, d0: Any, *, c0: int,
                 c1: int, interpret: bool = False) -> Any:
    """``ops.linalg._lu_pass`` as ONE Mosaic kernel over the panel in
    place: what a strip ``[c0, c1)`` changed, read once and written
    once.

    ``x`` is the (N, nb) panel, aliased in and out; ``st`` the factored
    strip, transposed.  A grid step holds a block of ``_LU_PASS_ROWS``
    rows of the columns from the strip's lane tile to the right edge
    (``new`` has these columns).  Where the block came in it puts the
    strip into its columns and stores the rows that moved (``new[j]``
    into row ``rows[j]``, in order, outside the strip's own columns: the
    first step of a pass lists the moved rows by block in SMEM, so a
    step walks its own and not all 2 w); then it walks the block once,
    chunk by chunk, to where it goes out: rows under the block row take
    ``- L @ U`` right of the strip (L the strip's columns of the chunk,
    U = the last w rows of ``new``; K = w on the MXU at ``highest``),
    every other entry goes as it is.  Last it hands out the NEXT
    strip's columns of the block, transposed.  The blocks of rows above
    the one holding ``d0`` are never brought in: their grid steps name
    the first block that is and do nothing, so those rows of the next
    strip hold nothing (no strip reads a row above its first).  The
    columns left of the strip's lane tile are not touched.  Returns (the
    panel, the next strip or None after the panel's last).  Shapes:
    :func:`lu_pass_fits`.  Reads no parameter; ``interpret`` is for the
    tests on the CPU."""
    n, nb = x.shape
    w = c1 - c0
    lo = lu_pass_window(c0)
    width = nb - lo
    blk = _LU_PASS_ROWS

    def first(i, d0_ref):
        return jnp.maximum(i, d0_ref[0] // blk)

    def window(i, d0_ref, rows_ref):
        # every dimension by its first element: a window's first column
        # is no multiple of its width
        return first(i, d0_ref) * blk, lo

    def strip(i, d0_ref, rows_ref):
        return 0, first(i, d0_ref)

    def whole(i, d0_ref, rows_ref):
        return 0, 0

    panel = pl.BlockSpec((pl.Element(blk), pl.Element(width)), window)
    out_shape = [jax.ShapeDtypeStruct((n, nb), x.dtype)]
    out_specs = [panel]
    if c1 < nb:
        out_shape.append(jax.ShapeDtypeStruct((w, n), x.dtype))
        out_specs.append(pl.BlockSpec((w, blk), strip))
    kernel = functools.partial(_lu_pass_kernel, w=w, off=c0 - lo)
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // blk,),
            in_specs=[panel, pl.BlockSpec((w, blk), strip),
                      pl.BlockSpec((2 * w, width), whole)],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((LU_PASS_TILE, blk), x.dtype),
                            pltpu.SMEM((n // blk,), jnp.int32),
                            pltpu.SMEM((2 * w,), jnp.int32)]),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_lu_pass_vmem_bytes(w, nb)),
        name="lu_pass_vmem", interpret=interpret,
    )(jnp.reshape(d0, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      x, st, new)
    return (out[0], out[1]) if c1 < nb else (out[0], None)


# ---------------------------------------------------------------------------
# LU's UPDATE of one block column right of a panel: the rows the panel's
# pivots moved and the product under the block row, in one walk
# ---------------------------------------------------------------------------

#: rows of the column (and of the panel) one grid step of an update holds
_LU_UPDATE_ROWS = 512


def _lu_update_vmem_bytes(nb: int, width: int) -> int:
    """The VMEM one update kernel asks for: a block of rows of the
    column coming in and one going out and a block of the panel, each
    twice (the pipeline's two buffers), the moved rows likewise, the
    product and its operands split for the MXU's passes, and room for
    Mosaic's own scratch."""
    blk = _LU_UPDATE_ROWS
    return (4 * (4 * blk * width + 2 * blk * nb + 2 * 2 * nb * width)
            + 4 * blk * width + 6 * (blk * nb + nb * width) + (4 << 20))


def lu_update_fits(n: int, nb: int, width: int) -> bool:
    """The shape rule of :func:`lu_update_vmem` for a (n, width) column
    right of a (n, nb) panel: whole blocks of rows, whole lanes of
    columns, a working set the kernel may ask for."""
    return (n % _LU_UPDATE_ROWS == 0 and nb % 128 == 0 and width % 128 == 0
            and n >= 2 * nb
            and _lu_update_vmem_bytes(nb, width) <= _LU_STRIP_VMEM_MAX)


def _lu_update_kernel(r_ref, rows_ref, c_ref, l_ref, new_ref, out_ref,
                      head_ref, next_ref, *, nb: int):
    blk = c_ref.shape[0]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        # the rows that moved, listed by the block of rows that holds
        # them, each list in the order of ``rows``: once an update
        def clear(b, carry):
            head_ref[b] = -1
            return carry

        def push(k, carry):
            j = 2 * nb - 1 - k
            b = rows_ref[j] // blk
            next_ref[j] = head_ref[b]
            head_ref[b] = j
            return carry

        jax.lax.fori_loop(0, pl.num_programs(0), clear, 0)
        jax.lax.fori_loop(0, 2 * nb, push, 0)

    def put(j):
        # one moved row: the pivots' rows first, then the block row (a
        # pivot row inside the block row ends as its row of U)
        c_ref[pl.ds(rows_ref[j] - i * blk, 1), :] = new_ref[pl.ds(j, 1), :]
        return next_ref[j]

    jax.lax.while_loop(lambda j: j >= 0, put, head_ref[i])
    # the block's first row under the block row
    under = r_ref[0] + nb - i * blk

    @pl.when(under >= blk)          # none: the block goes out as it is
    def _():
        out_ref[...] = c_ref[...]

    @pl.when(under < blk)
    def _():
        t = c_ref[...]
        prod = jnp.dot(l_ref[...], new_ref[nb:, :],
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        pos = jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
        out_ref[...] = jnp.where(pos >= under, t - prod, t)


def lu_update_vmem(l: Any, c: Any, rows: Any, new: Any, r: Any, *,
                   interpret: bool = False) -> Any:
    """``ops.linalg._lu_update`` as ONE Mosaic kernel: what panel k
    changes in one block column right of it, the column read once and
    written once.

    ``c`` is the (N, width) column; ``l`` the (N, nb) factored panel;
    ``new`` the 2 nb rows that moved, ``new[j]`` for row ``rows[j]``:
    the panel's pivot rows, then its block row ``r .. r + nb - 1``,
    which is U already (solved against L_kk by the caller).  A grid
    step holds a block of ``_LU_UPDATE_ROWS`` rows of the column and of
    the panel.  Where the block came in it stores the rows that moved,
    in order (the first step lists them by block in SMEM, so a step
    walks its own and not all 2 nb); then the rows under the block row
    take ``- L @ U`` (K = nb on the MXU at ``highest``) and every other
    row goes out as it is.  ``new`` stays in VMEM for the whole walk.
    The blocks above the one holding ``r`` hold no row that moved and
    go through with their bits, and no block of the panel is brought in
    for them (their steps name the first block that is).  The column is
    NOT aliased to the result: the runtime does not donate a task's
    column, so a kernel that wrote its operand would have XLA copy the
    whole column first, which costs more than passing the upper blocks
    through (PERF.md section 5).  Shapes: :func:`lu_update_fits`.
    Reads no parameter; ``interpret`` is for the tests on the CPU."""
    n, width = c.shape
    nb = l.shape[1]
    blk = _LU_UPDATE_ROWS

    def block(i, r_ref, rows_ref):
        return i, 0

    def active(i, r_ref, rows_ref):
        return jnp.maximum(i, r_ref[0] // blk), 0

    def whole(i, r_ref, rows_ref):
        return 0, 0

    return pl.pallas_call(
        functools.partial(_lu_update_kernel, nb=nb),
        out_shape=jax.ShapeDtypeStruct((n, width), c.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // blk,),
            in_specs=[pl.BlockSpec((blk, width), block),
                      pl.BlockSpec((blk, nb), active),
                      pl.BlockSpec((2 * nb, width), whole)],
            out_specs=pl.BlockSpec((blk, width), block),
            scratch_shapes=[pltpu.SMEM((n // blk,), jnp.int32),
                            pltpu.SMEM((2 * nb,), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_lu_update_vmem_bytes(nb, width)),
        name="lu_update_vmem", interpret=interpret,
    )(jnp.reshape(r, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      c, l, new)


# --------------------------------------------------------------------- #
# the 1D stencil's step of one tile (ops/stencil_1d.py)                 #
# --------------------------------------------------------------------- #
#: bytes of one block of rows of the tile a grid step of
#: :func:`stencil_tile_vmem` holds (the block in, the block out, each
#: twice for the pipeline, and the shifted copies a step makes)
_STENCIL_BLOCK_BYTES = 2 << 20
_STENCIL_LANES = 128


def _stencil_rows(rows: int, nb: int) -> int:
    """Rows a grid step holds: whole lanes of the ghosts' row form (a
    multiple of 128), about ``_STENCIL_BLOCK_BYTES`` of the tile."""
    blk = max(_STENCIL_LANES, _STENCIL_BLOCK_BYTES // (4 * nb)
              // _STENCIL_LANES * _STENCIL_LANES)
    while rows % blk and blk > _STENCIL_LANES:
        blk -= _STENCIL_LANES
    return blk


def _stencil_vmem_bytes(blk: int, nb: int) -> int:
    """What a grid step may ask for: the block in and out, twice each
    for the pipeline, the rolled copies and the sums of a step, and
    room for Mosaic's own scratch."""
    return 10 * blk * nb * 4 + (8 << 20)


def stencil_fits(rows: int, nb: int, radius: int) -> bool:
    """The shape rule of :func:`stencil_tile_vmem`: whole blocks of
    rows, whole lanes of columns, two distinct edge slabs, ghosts that
    stay inside a slab, and a working set the kernel may ask for."""
    return (rows % _STENCIL_LANES == 0 and nb % _STENCIL_LANES == 0
            and nb >= 2 * _STENCIL_LANES and radius <= _STENCIL_LANES
            and _stencil_vmem_bytes(_stencil_rows(rows, nb), nb)
            <= _LU_STRIP_VMEM_MAX)


def _stencil_kernel(x_ref, l_ref, r_ref, o_ref, *, weights):
    rad = len(weights) // 2
    blk, nb = x_ref.shape
    lanes = _STENCIL_LANES
    x = x_ref[...]
    diag = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)

    def column(row):
        # a (1, blk) row of a ghost as the (blk, 1) column it is
        return jnp.sum(jnp.where(diag, jnp.broadcast_to(row, (blk, blk)),
                                 0.0), axis=1, keepdims=True)

    left = [column(l_ref[k:k + 1, :]) for k in range(rad)]
    right = [column(r_ref[k:k + 1, :]) for k in range(rad)]
    lane = jax.lax.broadcasted_iota(jnp.int32, (blk, lanes), 1)
    acc = head = tail = None
    for d, w in enumerate(weights):
        s = d - rad         # this term is column j + s of [left | x | right]
        t = x if s == 0 else pltpu.roll(x, (-s) % nb, 1)
        th, tt = t[:, :lanes], t[:, nb - lanes:]
        for j in range(-s):             # columns the roll wrapped round
            th = jnp.where(lane == j, left[rad + j + s], th)
        for j in range(s):
            tt = jnp.where(lane == lanes - s + j, right[j], tt)
        acc = w * t if acc is None else acc + w * t
        head = w * th if head is None else head + w * th
        tail = w * tt if tail is None else tail + w * tt
    o_ref[...] = acc
    o_ref[:, :lanes] = head
    o_ref[:, nb - lanes:] = tail


def stencil_tile_vmem(x: Any, left: Any, right: Any, *, weights: tuple,
                      interpret: bool = False) -> Any:
    """``ops.linalg._stencil_tile`` as ONE Mosaic kernel: the tile read
    once and written once.

    ``x`` is the (rows, nb) tile, ``left`` and ``right`` its two ghost
    regions as (radius, rows) arrays (zeros: no neighbour).  A grid step
    holds a block of rows: every term of the sum is the block rolled
    along the lanes (the XLU; no shifted copy goes to memory), summed
    left to right as the XLA form does; the ``radius`` columns a roll
    wrapped round are put right in the first and the last 128 lanes
    only, from the ghosts, whose row form is turned into columns in
    VMEM.  Shapes: :func:`stencil_fits`.  Reads no parameter;
    ``interpret`` is for the tests on the CPU."""
    rows, nb = x.shape
    rad = len(weights) // 2
    blk = _stencil_rows(rows, nb)
    return pl.pallas_call(
        functools.partial(_stencil_kernel, weights=weights),
        out_shape=jax.ShapeDtypeStruct((rows, nb), x.dtype),
        grid=(rows // blk,),
        in_specs=[pl.BlockSpec((blk, nb), lambda i: (i, 0)),
                  pl.BlockSpec((rad, blk), lambda i: (0, i)),
                  pl.BlockSpec((rad, blk), lambda i: (0, i))],
        out_specs=pl.BlockSpec((blk, nb), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_stencil_vmem_bytes(blk, nb)),
        name="stencil_tile_vmem", interpret=interpret,
    )(x, left, right)
