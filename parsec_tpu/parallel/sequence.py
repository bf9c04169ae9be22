"""Ulysses-style sequence parallelism: all-to-all head<->sequence reshard.

The second canonical long-context strategy (besides the ring): attention
wants full sequence per head, the rest of the model wants full heads per
sequence chunk. ``lax.all_to_all`` over the sp axis converts
[B, H, T/sp, D] <-> [B, H/sp, T, D] in one fused ICI collective, attention
runs locally on full sequences, then the inverse all-to-all restores the
layout (ref capability mapping: SURVEY.md §5.7).
"""
from __future__ import annotations

from typing import Any

from jax import lax

from .ring_attention import local_attention


def heads_to_sequence(x: Any, axis_name: str = "sp") -> Any:
    """[B, H, T_local, Dh] -> [B, H_local, T, Dh]: scatter heads, gather seq."""
    return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def sequence_to_heads(x: Any, axis_name: str = "sp") -> Any:
    """[B, H_local, T, Dh] -> [B, H, T_local, Dh]: inverse reshard."""
    return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def ulysses_attention(q: Any, k: Any, v: Any, axis_name: str = "sp",
                      causal: bool = True) -> Any:
    """Sequence-parallel attention via all-to-all resharding.

    q/k/v: [B, H, T_local, Dh] (H divisible by the sp axis size).
    """
    sp = lax.axis_size(axis_name)
    assert q.shape[1] % sp == 0, \
        f"ulysses needs heads ({q.shape[1]}) divisible by sp ({sp})"
    qg = heads_to_sequence(q, axis_name)
    kg = heads_to_sequence(k, axis_name)
    vg = heads_to_sequence(v, axis_name)
    out = local_attention(qg, kg, vg, causal=causal)
    return sequence_to_heads(out, axis_name)
