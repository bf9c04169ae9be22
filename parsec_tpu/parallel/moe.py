"""Expert parallelism: MoE feed-forward with experts sharded over ep.

Each ep shard owns E/ep experts; every token is evaluated against the local
experts and the gate-weighted contributions are combined with a psum over
the ep axis. This is the dense-dispatch formulation (compute and expert
memory shard over ep; no capacity dropping), the robust baseline the
sparse all-to-all dispatch optimizes later. Differentiable end-to-end.

The reference has no MoE analog — its nearest mechanisms are tabular/hash
irregular distributions + dynamic DTD placement (SURVEY.md §2.8); this is
the mesh-native realization.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax


def moe_ffn(x: Any, gate_w: Any, w1: Any, w2: Any,
            axis_name: str = "ep", top_k: int = 2,
            gate_logits: Any = None) -> Any:
    """x: [..., D]; gate_w: [D, E_total] (replicated); w1: [E_local, D, F];
    w2: [E_local, F, D]. Returns [..., D]. Pass precomputed ``gate_logits``
    to share the gating einsum with the load-balance loss."""
    E_local = w1.shape[0]
    ep = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    E_total = E_local * ep

    logits = (gate_logits if gate_logits is not None
              else jnp.einsum("...d,de->...e", x, gate_w))  # [..., E_total]
    # top-k gating with renormalized probabilities (straight-through mask)
    probs = jax.nn.softmax(logits, axis=-1)
    if top_k < E_total:
        thresh = jax.lax.top_k(probs, top_k)[0][..., -1:]
        mask = probs >= thresh
        probs = probs * mask
        probs = probs / (probs.sum(axis=-1, keepdims=True) + 1e-9)
    local_probs = lax.dynamic_slice_in_dim(probs, idx * E_local, E_local,
                                           axis=-1)  # [..., E_local]
    h = jnp.einsum("...d,edf->...ef", x, w1,
                   preferred_element_type=jnp.float32)
    h = jax.nn.gelu(h)
    y = jnp.einsum("...ef,efd->...ed", h, w2,
                   preferred_element_type=jnp.float32)
    out = jnp.einsum("...ed,...e->...d", y, local_probs.astype(y.dtype))
    out = lax.psum(out, axis_name)
    return out.astype(x.dtype)


def load_balance_loss(gate_logits: Any, axis_name: str = "ep") -> Any:
    """Auxiliary load-balancing loss (Switch-style: fraction * prob)."""
    probs = jax.nn.softmax(gate_logits, axis=-1)
    E = probs.shape[-1]
    # mean prob per expert and fraction of tokens argmax-routed per expert
    mean_prob = probs.reshape(-1, E).mean(axis=0)
    hard = jax.nn.one_hot(jnp.argmax(probs, axis=-1), E)
    frac = hard.reshape(-1, E).mean(axis=0)
    return E * jnp.sum(mean_prob * frac)
