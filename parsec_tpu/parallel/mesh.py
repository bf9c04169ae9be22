"""Device mesh construction with the five canonical parallel axes.

TPU-native scaling model (SURVEY.md §5.8): pick a mesh, annotate shardings,
let XLA insert collectives over ICI. Axes: dp (data), pp (pipeline stages),
tp (tensor/heads), sp (sequence/context), ep (experts). Any axis may be
size 1 — the sharding code paths stay identical.

Reduced-precision collectives (ISSUE 14, the EQuARX recipe — arxiv
2506.17615): :class:`ErrorFeedback` + :func:`reduced_precision_sum` /
:func:`two_level_allreduce` quantize each contribution AT THE REDUCTION
BOUNDARY (blockwise bf16 or int8-with-per-block-scale, sharing the wire
codecs in comm/wire.py so lane and wire round identically) and carry
the residual of each quantized send into the next contribution of the
same logical buffer — iterative workloads don't drift: the quantization
error is fed back, not discarded. The wave collective lane
(dsl/ptg/wave_dist.py, ``wave_reduce_dtype``) rides these helpers.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

AXES = ("dp", "pp", "tp", "sp", "ep")

#: declared lock discipline (analysis/lock_check.py): the error-feedback
#: accumulator is per-instance mutable state shared between whichever
#: threads drive the reduction (SPMD rank threads deposit concurrently
#: into one lane) — residuals live under the instance lock
_GUARDED_BY = {
    "ErrorFeedback._resid": "_lock",
}


def _factor(n: int, order: Sequence[str]) -> Dict[str, int]:
    """Greedy power-of-small-primes factoring of n over the axes in
    ``order`` (round-robin halving keeps the mesh balanced)."""
    sizes = {a: 1 for a in AXES}
    remaining = n
    # round-robin: repeatedly give the next axis the smallest prime factor
    i = 0
    while remaining > 1:
        p = _smallest_prime(remaining)
        sizes[order[i % len(order)]] *= p
        remaining //= p
        i += 1
    return sizes


def _smallest_prime(n: int) -> int:
    for p in (2, 3, 5, 7, 11, 13):
        if n % p == 0:
            return p
    return n


def make_mesh(n_devices: Optional[int] = None,
              sizes: Optional[Dict[str, int]] = None,
              devices: Optional[List] = None,
              order: Sequence[str] = ("dp", "tp", "sp", "pp", "ep")):
    """Build a 5-axis jax Mesh over ``n_devices`` (or explicit devices).

    With explicit ``sizes`` missing axes default to 1; otherwise n_devices
    is factored over ``order``.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devs = jax.devices()
        if n_devices is not None:
            if len(devs) < n_devices:
                raise RuntimeError(
                    f"make_mesh needs {n_devices} devices, the default "
                    f"platform has {len(devs)}; pass devices= to build "
                    f"the mesh elsewhere")
            devs = devs[:n_devices]
    else:
        devs = list(devices)
    n = len(devs)
    if sizes is None:
        sizes = _factor(n, order)
    else:
        sizes = {**{a: 1 for a in AXES}, **sizes}
    total = int(np.prod([sizes[a] for a in AXES]))
    assert total == n, f"mesh sizes {sizes} != {n} devices"
    arr = np.array(devs).reshape([sizes[a] for a in AXES])
    return Mesh(arr, AXES)


def spec(*axes) -> "object":
    """PartitionSpec shorthand."""
    from jax.sharding import PartitionSpec as P
    return P(*axes)


# -- reduced-precision collectives with error feedback (ISSUE 14) -------
class ErrorFeedback:
    """Per-boundary error-feedback accumulator (EQuARX): for each
    logical buffer (caller-chosen ``key``) the residual of the last
    quantized send is retained and folded into the NEXT contribution
    before it quantizes, so repeated reductions of the same buffer
    converge to the full-precision result instead of accumulating
    bias. A key whose contribution shape changes starts fresh (it is a
    different buffer). Thread-safe: SPMD rank threads share one lane."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._resid: Dict[Any, np.ndarray] = {}

    def compensate(self, key: Any, arr: np.ndarray, codec: str,
                   qdq) -> np.ndarray:
        """Quantize ``arr`` through ``qdq(x, codec)`` with feedback:
        returns the quantized-dequantized values that should travel,
        retaining (folded contribution - sent values) for next time."""
        arr = np.asarray(arr)
        with self._lock:
            prev = self._resid.get(key)
            folded = (arr + prev if prev is not None
                      and prev.shape == arr.shape
                      and prev.dtype == arr.dtype else arr)
            out = qdq(folded, codec)
            self._resid[key] = folded - out
        return out

    def reset(self, key: Any = None) -> None:
        with self._lock:
            if key is None:
                self._resid.clear()
            else:
                self._resid.pop(key, None)

    def keys(self) -> List[Any]:
        with self._lock:
            return list(self._resid)


def _quant_codec_of(reduce_dtype: Optional[str]) -> Optional[str]:
    """Map a ``wave_reduce_dtype`` knob value to a registered quantized
    wire codec name (None = full precision)."""
    from ..comm import wire
    return wire.normalize_quant_codec(reduce_dtype or "")


# -- jit-native quantize hop (ISSUE 17) ---------------------------------
def qdq_jax(x: Any, codec: str) -> Any:
    """Traceable quantize-dequantize: jnp/lax ops only, and BIT-FOR-BIT
    the values :func:`wire.qdq_array` delivers (asserted by the parity
    test) — same RNE bf16 arithmetic on the raw uint32 bits, same
    blockwise absmax/127 f32 scales.  Usable inside a jit/shard_map
    body, so the reduction-boundary quantize lowers into the compiled
    collective instead of bouncing through host numpy."""
    import jax.numpy as jnp
    from jax import lax
    from ..comm.wire import QUANT_BLOCK
    if codec == "qbf16":
        dt = jnp.asarray(x).dtype
        u = lax.bitcast_convert_type(jnp.asarray(x, jnp.float32),
                                     jnp.uint32)
        # RNE: add 0x7FFF + the LSB of the kept half, then truncate —
        # the exact _enc_bf16 arithmetic, uint32 wraparound included
        q = ((u + jnp.uint32(0x7FFF)
              + ((u >> jnp.uint32(16)) & jnp.uint32(1)))
             >> jnp.uint32(16)).astype(jnp.uint16)
        f32 = lax.bitcast_convert_type(
            q.astype(jnp.uint32) << jnp.uint32(16), jnp.float32)
        return f32.astype(dt)
    if codec == "qint8":
        xa = jnp.asarray(x)
        n = xa.size
        nblocks = max(1, (n + QUANT_BLOCK - 1) // QUANT_BLOCK)
        xp = jnp.zeros(nblocks * QUANT_BLOCK, jnp.float32)
        xp = xp.at[:n].set(jnp.ravel(jnp.asarray(xa, jnp.float32)))
        xb = xp.reshape(nblocks, QUANT_BLOCK)
        # the divisor hides behind an optimization barrier: XLA:CPU
        # lowers division by a CONSTANT to reciprocal-multiply (1 ulp
        # off IEEE), which would break bit parity with the numpy codec
        # — an opaque runtime divisor keeps the correctly-rounded div
        c127 = lax.optimization_barrier(jnp.float32(127.0))
        scales = (jnp.abs(xb).max(axis=1) / c127).astype(jnp.float32)
        inv = jnp.where(scales > 0, 1.0 / scales, 0.0).astype(jnp.float32)
        q = jnp.clip(jnp.rint(xb * inv[:, None]),
                     -127, 127).astype(jnp.int8)
        deq = (q.astype(jnp.float32) * scales[:, None]).reshape(-1)[:n]
        return deq.reshape(xa.shape).astype(xa.dtype)
    raise ValueError(f"unknown quantized codec {codec!r}")


_QDQ_JIT: Dict[str, Any] = {}


def _qdq_native(arr: np.ndarray, codec: str) -> np.ndarray:
    """Numpy-in/numpy-out wrapper over the jit-compiled ``qdq_jax``
    (one compiled callable per codec, cached) — the drop-in boundary
    hop for the collective helpers below."""
    fn = _QDQ_JIT.get(codec)
    if fn is None:
        import jax
        fn = jax.jit(lambda v, c=codec: qdq_jax(v, c))
        _QDQ_JIT[codec] = fn
    a = np.ascontiguousarray(arr)
    # both codecs narrow through f32 before encoding (exactly what the
    # wire does) — feed f32 so a disabled-x64 jax cannot silently
    # truncate, and widen back to the caller's dtype on the way out
    out = np.asarray(fn(a.astype(np.float32, copy=False)))
    return out.astype(a.dtype, copy=False).reshape(a.shape)


def reduced_precision_sum(contribs: Sequence[np.ndarray],
                          reduce_dtype: Optional[str] = None,
                          feedback: Optional[ErrorFeedback] = None,
                          keys: Optional[Sequence[Any]] = None,
                          native: bool = True) -> np.ndarray:
    """Sum of per-participant contributions with quantize-at-the-
    boundary: each contribution is quantized (bf16 / int8 blockwise,
    exactly the wire codecs) before it enters the reduction —
    modelling what a reduced-precision all-reduce would move — and the
    accumulation itself stays full precision. ``feedback``/``keys``
    enable per-contributor error feedback (``keys[i]`` names
    contributor i's logical buffer). ``reduce_dtype`` None/"" keeps the
    exact full-precision sum (bit-for-bit the naive sum).  ``native``
    (the default) routes the boundary quantize through the jit-compiled
    :func:`qdq_jax` hop — bit-identical values (the parity contract),
    XLA-lowered arithmetic; ``native=False`` falls back to the eager
    host-numpy wire codec (kept for parity testing only)."""
    from ..comm import wire
    codec = _quant_codec_of(reduce_dtype)
    if codec is None:
        out = np.zeros_like(np.asarray(contribs[0]))
        for c in contribs:
            out = out + np.asarray(c)
        return out
    qdq = _qdq_native if native else wire.qdq_array
    out = None
    for i, c in enumerate(contribs):
        c = np.asarray(c)
        if feedback is not None and keys is not None:
            q = feedback.compensate(keys[i], c, codec, qdq)
        else:
            q = qdq(c, codec)
        out = q if out is None else out + q
    return out


def two_level_allreduce(shards: Sequence[np.ndarray],
                        group_size: int,
                        reduce_dtype: Optional[str] = None,
                        feedback: Optional[ErrorFeedback] = None,
                        key: Any = None,
                        native: bool = True) -> np.ndarray:
    """Hierarchical all-reduce: contributions reduce FULL-precision
    inside each ``group_size``-wide group (level 1 — the intra-mesh
    XLA psum over ICI, where bandwidth is plentiful), each group's
    partial sum quantizes at the group boundary (level 2 — the
    inter-rank hop over the wire, where it is not), and the quantized
    partials sum to the replicated result. With ``feedback`` set, each
    group's boundary residual is carried into its next partial under
    ``(key, group index)`` — the EQuARX error-feedback recipe. With
    ``reduce_dtype`` None/"" this is exactly the flat sum.  ``native``
    (the default) lowers the boundary quantize through the jit-compiled
    :func:`qdq_jax` hop (bit-identical values, XLA arithmetic);
    ``native=False`` is the eager host-numpy reference path."""
    n = len(shards)
    groups = [list(range(g, min(g + group_size, n)))
              for g in range(0, n, group_size)]
    partials = []
    for gi, members in enumerate(groups):
        part = np.asarray(shards[members[0]]).copy()
        for m in members[1:]:
            part += np.asarray(shards[m])
        partials.append(part)
    keys = [(key, gi) for gi in range(len(groups))] \
        if feedback is not None else None
    return reduced_precision_sum(partials, reduce_dtype,
                                 feedback=feedback, keys=keys,
                                 native=native)


def sync_axes(leaf_spec, mesh_axes: Sequence[str] = AXES) -> Tuple[str, ...]:
    """Mesh axes a parameter is REPLICATED over (its gradients must be
    psum'd across exactly these after manual-collective backprop)."""
    used = set()
    for entry in tuple(leaf_spec):
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return tuple(a for a in mesh_axes if a not in used)


def _vma_of(x):
    import jax
    try:
        return set(jax.typeof(x).vma)
    except (AttributeError, TypeError):
        return None


def _pcast_varying(x, axes):
    from jax import lax
    return lax.pcast(x, axes, to="varying")


def match_vma(x, ref):
    """Promote ``x``'s varying-manual-axes (VMA) to cover ``ref``'s.

    Under check_vma=True, lax.scan requires carry input/output types to
    match exactly — fresh-zeros initial carries are 'unvarying' while the
    loop body makes them varying. Promote initials with this before scan.
    """
    cur, want_src = _vma_of(x), _vma_of(ref)
    if cur is None or want_src is None:
        return x
    want = tuple(sorted(want_src - cur))
    return _pcast_varying(x, want) if want else x


def vary_on(x, axes, like=None):
    """Promote ``x`` to be varying on ``axes`` (plus ``like``'s VMA)."""
    cur = _vma_of(x)
    if cur is None:
        return x
    target = set(axes)
    if like is not None:
        target |= _vma_of(like) or set()
    want = tuple(sorted(target - cur))
    return _pcast_varying(x, want) if want else x


def xrank_mesh(devices):
    """One-axis ("xr") mesh over per-rank lane devices: the global
    mesh a cross-rank SPMD stage (stagec/xrank.py, ISSUE 20) compiles
    its shard_map program over.  Position p of the axis IS the p-th
    participating rank, so an ``all_gather`` over "xr" moves boundary
    tiles from producer-rank lanes to every participant in-program —
    the collective that replaces the serialized wire activation."""
    import numpy as _np
    from jax.sharding import Mesh
    return Mesh(_np.array(list(devices)), ("xr",))


def shard_map_compat(f, mesh, in_specs, out_specs):
    """jax.shard_map with VMA (varying-manual-axes) tracking ON.

    check_vma=True is load-bearing for gradient correctness, not just
    checking: with it, psum transposes via the replication-aware rule and
    jax.grad of a REPLICATED leaf comes out already psum'd over exactly
    the axes its contributions were partial on — including the subtle
    cases (axes the forward never touches produce identity, mixed
    redundant+partial paths split correctly). With check_vma=False, psum
    transposes to psum and no per-leaf psum/pmean recipe is exact.
    """
    import jax
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=True)
