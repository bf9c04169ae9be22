"""Ring attention: sequence-parallel exact attention over an ICI ring.

Each sp shard holds a local Q/K/V sequence chunk; K/V blocks rotate around
the ring with ``lax.ppermute`` while a flash-style online softmax
accumulates (running max + denominator), so memory stays O(T_local) and
the collective rides neighbor links. Causal masking uses global positions
reconstructed from the ring step. Differentiable end-to-end (scan +
ppermute are AD-capable), so the same code serves training.

This fills the reference's sequence-parallelism gap (SURVEY.md §2.8, §5.7)
the TPU-native way.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax


def ring_attention(q: Any, k: Any, v: Any, axis_name: str = "sp",
                   causal: bool = True, scale: float | None = None,
                   use_pallas: bool | None = None) -> Any:
    """q, k, v: [B, H, T_local, Dh] per-shard chunks (inside shard_map over
    ``axis_name``). Returns [B, H, T_local, Dh].

    ``use_pallas`` selects the per-step local compute: the Pallas flash
    kernel with exported softmax stats (no [T_local, T_local] score
    materialization — O(T_local) memory in the forward) vs the jnp
    online-softmax path. None = auto (flash on TPU for 128-lane-aligned
    shapes). The flash path's backward recomputes through the jnp ring
    (same activation cost as the jnp path's AD; the win is the forward)."""
    B, H, Tl, Dh = q.shape
    if use_pallas is None:  # auto: aligned shapes + the pallas policy knob
        from ..ops import pallas_kernels as _pk
        use_pallas = (Tl % 128 == 0 and Dh % 8 == 0
                      and _pk is not None and _pk.use_pallas())
    if use_pallas:  # explicit True runs the kernel even off-TPU (interpret)
        if scale is None:
            scale = Dh ** -0.5
        return _ring_flash(q, k, v, axis_name, causal, float(scale))
    return _ring_jnp(q, k, v, axis_name, causal, scale)


def _ring_jnp(q: Any, k: Any, v: Any, axis_name: str,
              causal: bool, scale: float | None) -> Any:
    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, H, Tl, Dh = q.shape
    if scale is None:
        scale = Dh ** -0.5
    q_pos = idx * Tl + jnp.arange(Tl)

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(carry, t):
        k_blk, v_blk, m, l, acc = carry
        # the block we hold at step t originated on rank (idx - t) mod sp
        src = (idx - t) % sp
        k_pos = src * Tl + jnp.arange(Tl)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk, preferred_element_type=jnp.float32)
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, m_new, l_new, acc_new), None

    from .mesh import match_vma
    m0 = match_vma(jnp.full((B, H, Tl), -jnp.inf, dtype=jnp.float32), q)
    l0 = match_vma(jnp.zeros((B, H, Tl), dtype=jnp.float32), q)
    acc0 = match_vma(jnp.zeros((B, H, Tl, Dh), dtype=jnp.float32), q)
    (k_f, v_f, m, l, acc), _ = lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(sp))
    out = acc / l[..., None]
    return out.astype(q.dtype)


# -- flash ring: Pallas local blocks + cross-shard stats merge -------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, axis_name: str, causal: bool, scale: float):
    return _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale)


def _ring_flash_fwd_impl(q, k, v, axis_name: str, causal: bool,
                         scale: float):
    from ..ops.pallas_kernels import _NEG_INF, flash_attention_stats
    from .mesh import match_vma

    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, H, Tl, Dh = q.shape
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def _norm(o, m, l):
        # one output type for every switch branch: f32 o, q's vma on all
        return (match_vma(o.astype(jnp.float32), q),
                match_vma(m, q), match_vma(l, q))

    def full_blk(kv):
        kb, vb = kv
        return _norm(*flash_attention_stats(q, kb, vb, causal=False,
                                            scale=scale))

    def diag_blk(kv):
        kb, vb = kv
        return _norm(*flash_attention_stats(q, kb, vb, causal=causal,
                                            scale=scale))

    def skip_blk(kv):
        return _norm(jnp.zeros((B, H, Tl, Dh), jnp.float32),
                     jnp.full((B, H, Tl), _NEG_INF, jnp.float32),
                     jnp.zeros((B, H, Tl), jnp.float32))

    def step(carry, t):
        k_blk, v_blk, m, l, acc = carry
        src = (idx - t) % sp
        # block relation to the diagonal decides masking: past shards
        # attend fully, own shard causally, future shards not at all
        if causal:
            sel = jnp.where(src == idx, 1, jnp.where(src > idx, 2, 0))
            o_b, m_b, l_b = lax.switch(sel, [full_blk, diag_blk, skip_blk],
                                       (k_blk, v_blk))
        else:  # static: every block attends fully — no dead branches
            o_b, m_b, l_b = full_blk((k_blk, v_blk))
        # merge this block's normalized partial into the running state
        m_new = jnp.maximum(m, m_b)
        c_run = jnp.exp(m - m_new) * l
        c_blk = jnp.exp(m_b - m_new) * l_b
        acc_new = acc * jnp.exp(m - m_new)[..., None] \
            + o_b * c_blk[..., None]
        l_new = c_run + c_blk
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, m_new, l_new, acc_new), None

    m0 = match_vma(jnp.full((B, H, Tl), _NEG_INF, jnp.float32), q)
    l0 = match_vma(jnp.zeros((B, H, Tl), jnp.float32), q)
    acc0 = match_vma(jnp.zeros((B, H, Tl, Dh), jnp.float32), q)
    (k_f, v_f, m, l, acc), _ = lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(sp))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(q.dtype)


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, scale):
    return _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale), (q, k, v)


def _ring_flash_vjp_bwd(axis_name, causal, scale, res, g):
    # backward recomputes through the differentiable jnp ring — identical
    # math, so gradients are exact; activation memory matches the jnp
    # path's AD (the flash win is the forward's O(T_local) footprint)
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ring_jnp(q_, k_, v_, axis_name, causal, scale),
        q, k, v)
    return vjp(g)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def local_attention(q: Any, k: Any, v: Any, causal: bool = True,
                    scale: float | None = None,
                    use_pallas: bool | None = None) -> Any:
    """Plain single-shard attention (used by the Ulysses path after the
    head<->sequence all-to-all, and as the sp=1 reference).

    On TPU this dispatches to the Pallas flash kernel (2.7x the XLA
    attention on v5e at T=2048); the jnp path is the reference/fallback.
    ``use_pallas=False`` forces the jnp path (tests use it as the oracle);
    None = auto. Auto only fires when both sequence dims are 128-lane
    aligned (so every block _pick_block derives is a 128-multiple) and
    Dh is sublane-aligned — conservative bounds Mosaic always accepts.
    """
    B, H, T, Dh = q.shape
    Tk = k.shape[2]
    if use_pallas is None:
        use_pallas = T % 128 == 0 and Tk % 128 == 0 and Dh % 8 == 0
    if use_pallas:
        from ..ops import pallas_kernels as _pk
        if _pk is not None and _pk.use_pallas():
            return _pk.flash_attention(q, k, v, causal=causal, scale=scale)
    if scale is None:
        scale = Dh ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        # local-index convention (row i attends to keys 0..i), matching
        # the Pallas kernel when Tk != T
        mask = jnp.arange(T)[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
