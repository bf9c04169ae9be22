"""Reshape engine: lazy type/shape conversion across dataflow edges.

Reference behavior: when a producer's datatype differs from what a
consumer declares (e.g. full tile -> lower triangle), a *reshape promise*
(``parsec_datacopy_future_t``) is attached to the edge; the FIRST consumer
to need the data triggers the conversion, concurrent consumers of the same
(copy, type) dedup onto one promise, and the converted copy is released
with the promise (ref: parsec/parsec_reshape.c:1-771, promise structs
parsec/remote_dep.h:86-117; 18 dedicated tests under
tests/collections/reshape/).

TPU-native re-design: a "datatype" is a (dtype, shape, region) descriptor
(data/datatype.py); conversion is an XLA-fusable masked cast instead of an
MPI pack/unpack. Local and remote variants share the promise machinery:
the local trigger converts an existing host/device copy; the remote
variant is armed before the payload exists and converts on arrival.

A tile that lives on an accelerator is converted THERE, by one compiled
program named ``CONVERT`` (``jit_CONVERT`` in a device trace), through
the device holding it (``JaxDevice.convert``: reserved in its memory
accounting, counted in its ``stats``): no host round trip.  The copy it
makes belongs to no ``Data`` (``copy.data is None``), so a stage-in
takes its payload as it is and no LRU keeps it.  A promise taken with
``acquire`` is COUNTED: the PTG runtime takes one use for every local
successor of the produced tile that declares the type, where the tile
is produced, and gives it back when that successor completes
(``release``); with the last use the promise leaves the table and the
converted payload is dropped.  ``reshaped_copy`` is the uncounted form:
its promise lives until ``clear()`` or the taskpool's end.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from ..core.future import DataCopyFuture
from .data import Coherency, Data, DataCopy, is_device_array
from .datatype import Datatype, dtt_of_array

_now = time.monotonic_ns
#: dst datatype -> the jitted program converting a device tile to it
_programs: Dict[Datatype, Any] = {}


def conversion_program(dst: Datatype) -> Any:
    """The compiled conversion of a device array to ``dst``, one per
    datatype and process; it runs as ``jit_CONVERT``."""
    fn = _programs.get(dst)
    if fn is None:
        import jax

        def CONVERT(arr):
            return reshape_array(arr, dst, dtt_of_array(arr))

        fn = _programs[dst] = jax.jit(CONVERT)
    return fn


def reshape_array(arr: Any, dst: Datatype, src: Optional[Datatype] = None) -> Any:
    """Convert ``arr`` to datatype ``dst``: cast + region mask (+ reshape
    when element counts match). The conversion body is pure jnp/numpy —
    under jit XLA fuses it into the consumer (the relayout-kernel analog
    of ce.reshape)."""
    if src is None:
        src = dtt_of_array(arr)
    if arr.shape != tuple(dst.shape):
        if src.nb_elts != dst.nb_elts:
            raise ValueError(
                f"reshape {src.shape}->{dst.shape}: element counts differ")
        arr = arr.reshape(dst.shape)
    if np.dtype(src.dtype) != np.dtype(dst.dtype):
        arr = arr.astype(dst.dtype)
    if dst.region != "full" and dst.region != src.region:
        mask = dst.mask()
        if mask is not None:
            if isinstance(arr, np.ndarray):
                arr = np.where(mask, arr, np.zeros((), dtype=arr.dtype))
            else:
                import jax.numpy as jnp
                arr = jnp.where(jnp.asarray(mask), arr,
                                jnp.zeros((), dtype=arr.dtype))
    return arr


def _needs_reshape(copy: DataCopy, dst: Datatype) -> bool:
    src = copy.dtt
    if src is None:
        payload = copy.payload
        if payload is None:
            return True  # cannot prove compatibility; promise will decide
        src = dtt_of_array(payload)
    return not src.compatible_wire(dst)


class ReshapeRepo:
    """Per-taskpool table of reshape promises with dedup.

    Keyed by (source copy identity, its version, destination datatype):
    N consumers of one produced copy that declare the same [type=...]
    share ONE converted copy, converted once (ref: reshape dedup of
    concurrent promises, parsec_reshape.c setup_matching_reshape paths).
    A tile's device copy is one object from version to version, so the
    version is part of the key.
    """

    def __init__(self) -> None:
        self._promises: Dict[Tuple, DataCopyFuture] = {}
        # counted promises: key -> uses still out; id(converted copy)
        # -> (key, the device that made it or None)
        self._uses: Dict[Tuple, int] = {}
        self._counted: Dict[int, Tuple] = {}
        self._lock = threading.Lock()
        self.stats = {"local_promises": 0, "remote_promises": 0,
                      "conversions": 0, "hits": 0, "released": 0}

    # -- local reshape ------------------------------------------------------
    def reshaped_copy(self, copy: Optional[DataCopy], dst: Datatype,
                      es: Any = None) -> Optional[DataCopy]:
        """Return a copy matching ``dst``, converting lazily via a shared
        promise. Non-matching copies are never mutated — the original
        stays valid for consumers that want the producer's type."""
        return self._converted(copy, dst, es, use=False)

    def acquire(self, copy: Optional[DataCopy], dst: Datatype,
                es: Any = None) -> Optional[DataCopy]:
        """``reshaped_copy`` with one USE taken on the converted copy:
        whoever acquires gives it back with ``release``, and the last
        use out drops the promise and the converted payload."""
        return self._converted(copy, dst, es, use=True)

    def _converted(self, copy: Optional[DataCopy], dst: Datatype, es: Any,
                   use: bool) -> Optional[DataCopy]:
        if copy is None or copy.payload is None \
                or not _needs_reshape(copy, dst):
            return copy
        with _Pass(copy, es) as dev:
            conv = self._promise(copy, dst, dev, use).get_or_trigger()
            if use:
                with self._lock:
                    self._counted.setdefault(id(conv),
                                             (_key(copy, dst), dev))
            return conv

    def retain(self, conv: Optional[DataCopy]) -> None:
        """One more use of a copy ``acquire`` returned (a producer holds
        one while it hands the copy to its successors)."""
        with self._lock:
            ent = self._counted.get(id(conv))
            if ent is not None:
                self._uses[ent[0]] += 1

    def release(self, conv: Optional[DataCopy], drop: bool = True) -> None:
        """Give back one use; nothing for a copy that is not counted.
        The last use out takes the promise from the table and, unless
        ``drop`` is false (the reader WROTE the copy and hands it on: it
        is that task's now), drops the converted payload."""
        with self._lock:
            ent = self._counted.get(id(conv))
            if ent is None:
                return
            key, dev = ent
            self._uses[key] -= 1
            if self._uses[key] > 0:
                return
            del self._uses[key], self._counted[id(conv)]
            self._promises.pop(key, None)
            self.stats["released"] += 1
        nbytes = getattr(conv.payload, "nbytes", 0)
        if drop:
            conv.payload = None
        if dev is not None:
            dev.release_converted(nbytes)

    def _promise(self, copy: DataCopy, dst: Datatype, dev: Any,
                 use: bool = False) -> DataCopyFuture:
        """The shared promise converting ``copy`` to ``dst`` (local
        variant: the source payload already exists), the conversion made
        by ``dev`` (the accelerator holding the tile) when there is one;
        ``use`` counts one use."""
        key = _key(copy, dst)
        with self._lock:
            if use:
                self._uses[key] = self._uses.get(key, 0) + 1
            fut = self._promises.get(key)
            if fut is not None:
                self.stats["hits"] += 1
                if dev is not None:
                    dev.stats["reshape_hits"] += 1
                return fut

            def trigger(_spec, _copy=copy, _dst=dst):
                self.stats["conversions"] += 1
                if dev is not None:
                    # made where the tile lives; the copy is nobody's
                    # Data, so a stage-in takes its payload as it is
                    c = DataCopy(None, dev.device_index,
                                 payload=dev.convert(_copy.payload, _dst),
                                 dtt=_dst)
                    c.version = _copy.version
                    c.coherency = Coherency.OWNED
                    return c
                src_dtt = _copy.dtt or dtt_of_array(_copy.payload)
                arr = reshape_array(_copy.payload, _dst, src_dtt)
                return _detached_copy(arr, _dst, version=_copy.version)

            fut = DataCopyFuture(spec=dst, trigger_cb=trigger)
            self._promises[key] = fut
            self.stats["local_promises"] += 1
            return fut

    # -- remote reshape -----------------------------------------------------
    def incoming_promise(self, edge_key: Tuple, dst: Datatype
                         ) -> Tuple[DataCopyFuture, Callable[[Any], None]]:
        """Remote variant: the promise is armed BEFORE the payload exists
        (the receiver knows the consumer's type from its own dep lookup,
        ref: remote_dep_mpi_retrieve_datatype both-ends lookup). Returns
        (future, deliver); call ``deliver(ndarray)`` when the wire data
        arrives — consumers already waiting convert exactly once."""
        key = ("remote", edge_key, dst)
        with self._lock:
            ent = self._promises.get(key)
            if ent is not None:
                self.stats["hits"] += 1
                return ent, getattr(ent, "_deliver", lambda a: None)

            arrival = DataCopyFuture(spec=None)

            def trigger(_spec, _dst=dst):
                arr = arrival.get()  # blocks until wire data delivered
                self.stats["conversions"] += 1
                return _detached_copy(reshape_array(arr, _dst), _dst,
                                      version=1)

            fut = DataCopyFuture(spec=dst, trigger_cb=trigger)

            def deliver(arr: Any) -> None:
                if not arrival.is_ready():
                    arrival.set(arr)
                fut.trigger()

            fut._deliver = deliver  # type: ignore[attr-defined]
            self._promises[key] = fut
            self.stats["remote_promises"] += 1
            return fut, deliver

    def clear(self) -> None:
        with self._lock:
            self._promises.clear()
            self._uses.clear()
            self._counted.clear()

    def held(self) -> int:
        """Promises in the table (counted ones leave with their last
        use)."""
        with self._lock:
            return len(self._promises)


def _key(copy: DataCopy, dst: Datatype) -> Tuple:
    return (id(copy), copy.version, dst)


def _device_of(copy: DataCopy, es: Any) -> Any:
    """The accelerator device holding ``copy``'s payload, if it can
    convert there; None for a host payload or with no context."""
    if es is None or not is_device_array(copy.payload):
        return None
    for dev in getattr(getattr(es, "context", None), "devices", ()):
        if dev.device_index == copy.device_id:
            return dev if hasattr(dev, "convert") else None
    return None


class _Pass:
    """One trip through the reshape engine for a flow that declares a
    type: the phase ``reshape`` of the open root span's clock
    (``parsec:reshape`` in a profiler trace), and in every run the wall
    nanoseconds in ``reshape_ns`` / ``reshape_n`` of the device holding
    the tile.  Yields that device (None: a host tile)."""

    __slots__ = ("dev", "clock", "t0")

    def __init__(self, copy: DataCopy, es: Any) -> None:
        self.dev = _device_of(copy, es)
        self.clock = getattr(getattr(es, "context", None),
                             "_phase_clock", None)

    def __enter__(self) -> Any:
        self.t0 = _now()
        if self.clock is not None:
            self.clock.push("reshape", self.t0)
        return self.dev

    def __exit__(self, *exc) -> bool:
        t1 = _now()
        if self.clock is not None:
            self.clock.pop("reshape", at_ns=t1)
        if self.dev is not None:
            st = self.dev.stats
            st["reshape_ns"] += t1 - self.t0
            st["reshape_n"] += 1
        return False


def _detached_copy(arr: Any, dtt: Datatype, version: int = 1) -> DataCopy:
    d = Data(nb_elts=getattr(arr, "size", dtt.nb_elts))
    c = DataCopy(d, 0, payload=arr, dtt=dtt)
    c.version = version
    c.coherency = Coherency.OWNED
    d.attach_copy(c)
    return c
