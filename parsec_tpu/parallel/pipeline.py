"""Pipeline parallelism: GPipe-style microbatch schedule over the pp axis.

Each pp shard holds one stage's parameters; activations flow stage-to-stage
with ``lax.ppermute`` in a ``lax.scan`` over M + S - 1 ticks (M microbatches
through S stages), so the schedule compiles to one XLA loop with
neighbor-only ICI traffic. Differentiable: reverse-mode AD through the scan
reproduces the backward pipeline (the reference expresses pipelining as DAG
edges + per-device chores, SURVEY.md §2.8; this is the compiled-collective
equivalent).
"""
from __future__ import annotations

from typing import Any, Callable

import jax.numpy as jnp
from jax import lax


def gpipe(stage_fn: Callable[[Any, Any], Any], stage_params: Any,
          x_micro: Any, axis_name: str = "pp", with_aux: bool = False) -> Any:
    """Run the pipeline.

    stage_fn(stage_params, x) applies THIS shard's stage to one microbatch.
    x_micro: [M, mb, ...] microbatches (only stage 0's value is consumed).
    Returns [M, mb, ...] stage-S-1 outputs — valid ON THE LAST STAGE ONLY
    (other shards hold garbage; reduce with a masked psum, see
    models/train.py).

    with_aux: stage_fn returns (y, aux_scalar); gpipe accumulates aux only
    over the (stage, tick) pairs doing real work (bubble ticks run on
    garbage and are masked out) and returns (outs, aux_sum) where aux_sum
    is THIS stage's total over its layers x all microbatches.
    """
    S = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = x_micro.shape[0]
    steps = M + S - 1
    fwd = [(i, i + 1) for i in range(S - 1)]

    from .mesh import vary_on
    # scan carries become pp-varying through the stage params / axis_index;
    # promote the fresh-zeros initials to the matching VMA type
    target = (axis_name,)
    out0 = vary_on(jnp.zeros_like(x_micro), target, like=x_micro)
    buf0 = vary_on(jnp.zeros_like(x_micro[0]), target, like=x_micro)
    aux0 = vary_on(jnp.zeros((), jnp.float32), target, like=x_micro)

    def tick(carry, t):
        buf, outs, aux_sum = carry
        # stage 0 feeds microbatch t (while t < M); other stages consume
        # what arrived from the previous stage
        feed = x_micro[jnp.clip(t, 0, M - 1)]
        inp = jnp.where(idx == 0, feed, buf)
        if with_aux:
            y, aux = stage_fn(stage_params, inp)
            # stage idx works on microbatch t-idx at this tick
            work = (t - idx >= 0) & (t - idx < M)
            aux_sum = aux_sum + jnp.where(work, aux, 0.0)
        else:
            y = stage_fn(stage_params, inp)
        # drain: the last stage completed microbatch t-(S-1) at this tick
        mb = t - (S - 1)
        valid = (mb >= 0) & (mb < M)
        slot = jnp.clip(mb, 0, M - 1)
        outs = outs.at[slot].set(jnp.where(valid, y, outs[slot]))
        buf_next = lax.ppermute(y, axis_name, fwd) if S > 1 else buf
        return (buf_next, outs, aux_sum), None

    (_, outs, aux_sum), _ = lax.scan(tick, (buf0, out0, aux0),
                                     jnp.arange(steps))
    return (outs, aux_sum) if with_aux else outs


def last_stage_value(x: Any, axis_name: str = "pp") -> Any:
    """Reduce a per-shard value to the LAST pp stage's contribution,
    replicated everywhere (masked psum)."""
    S = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    return lax.psum(jnp.where(idx == S - 1, x, jnp.zeros_like(x)), axis_name)
