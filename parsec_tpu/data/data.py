"""Data substrate: versioned per-device data copies with coherency.

Reference behavior: ``parsec_data_t`` holds one ``parsec_data_copy_t`` per
device, each with a version, a reader count, and a coherency state in
{INVALID, OWNED, EXCLUSIVE, SHARED}; ownership moves to a copy on write
access and readers attach to valid copies
(ref: parsec/data_internal.h:57-81, parsec/data.h:27-31,
parsec_data_transfer_ownership_to_copy parsec/data.c:286-370).

TPU-native re-design: a copy's payload is a numpy array on the host device
or a jax.Array on an accelerator device. Transfers are jax.device_put /
np.asarray — asynchronous on TPU (dispatch returns immediately; readiness is
polled via jax's async semantics by the device module).
"""
from __future__ import annotations

import gc
import itertools
import threading
from enum import IntEnum
from typing import Any, Dict, List, Optional

from ..core.object import Obj

#: declared lock discipline, enforced by the concurrency lint
#: (parsec_tpu/analysis/lock_check.py; tools/parsec_lint.py runs it):
#: the copy map is read by worker, comm, and device threads while
#: stage-in/eviction/writeback mutate it — every touch goes through
#: Data._lock (construction and refcount-zero teardown are exempt)
_GUARDED_BY = {
    "Data._copies": "_lock",
}


def is_device_array(x: Any) -> bool:
    """A jax array (device-resident payload): stays on device through
    transports/stage-in; numpy arrays and scalars take host paths."""
    try:
        import jax
        return isinstance(x, jax.Array)
    except Exception:  # pragma: no cover - jax always present in-tree
        return False


def to_host(arr: Any) -> Any:
    """A writable host copy of ``arr`` that is the ONLY one.  A jax
    array keeps the host value of its first conversion for its own
    lifetime (on the TPU ``np.array(arr)`` is one copy cached in the
    array and a second handed out: for a pulled tile twice its bytes
    of host memory as long as the device copy lives), so the
    conversion is made through a second array object over the same
    device buffer, which is dropped here with its cache.  On the TPU
    that object and its cached value refer to each other, so only the
    cycle collector frees them, and a loop over a matrix's tiles makes
    none of the container allocations that wake it (6.7 GB of such
    garbage after a 6.7 GB pull; read on the chip, PR 46): the young
    generations, where the object still is, are collected here.  For
    the reader of a whole matrix (``TiledMatrix.to_numpy``), outside
    any timed path: the pulls inside a call (``pull_to_host``, an
    eviction's writeback) stay plain ``np.array``."""
    import numpy as np
    if not is_device_array(arr):
        return np.array(arr)
    import jax
    try:
        twin = jax.make_array_from_single_device_arrays(
            arr.shape, arr.sharding, [arr])
    except Exception:   # a sharded array: converted as it is
        return np.array(arr)
    out = np.array(twin)
    del twin
    gc.collect(1)
    return out


class Coherency(IntEnum):
    INVALID = 0
    OWNED = 1       # only valid version; other copies may be stale
    EXCLUSIVE = 2   # owned and no other copies exist
    SHARED = 3      # multiple valid copies


class FlowAccess(IntEnum):
    NONE = 0
    READ = 1
    WRITE = 2
    RW = 3


class DataCopy(Obj):
    """One incarnation of a Data on one device."""

    __slots__ = ("data", "device_id", "version", "readers", "coherency",
                 "payload", "flags", "dtt", "arena_chunk")

    def __init__(self, data: "Data", device_id: int, payload: Any = None,
                 dtt: Any = None) -> None:
        super().__init__()
        self.data = data
        self.device_id = device_id
        self.version = 0
        self.readers = 0
        self.coherency = Coherency.INVALID
        self.payload = payload
        self.dtt = dtt          # datatype/shape descriptor (see data/datatype.py)
        self.arena_chunk = None  # owning arena, for recycling on destruct

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (f"<DataCopy dev={self.device_id} v={self.version} "
                f"{Coherency(self.coherency).name} readers={self.readers}>")

    def _destruct(self) -> None:
        if self.arena_chunk is not None:
            self.arena_chunk.release_copy(self)
            self.arena_chunk = None
        if self.data is not None:
            self.data._detach_copy(self)
        self.payload = None
        super()._destruct()


class Data(Obj):
    """A logical datum with per-device copies (ref: parsec_data_t)."""

    _key_iter = itertools.count()

    def __init__(self, key: Any = None, collection: Any = None,
                 nb_elts: int = 0) -> None:
        super().__init__()
        self.key = key if key is not None else next(Data._key_iter)
        self.collection = collection  # owning data collection, if any
        self.nb_elts = nb_elts        # logical payload size in elements/bytes
        self.owner_device: int = -1
        self.preferred_device: int = -1
        self._copies: Dict[int, DataCopy] = {}
        self._lock = threading.RLock()

    # -- copy management ----------------------------------------------------
    def attach_copy(self, copy: DataCopy) -> None:
        with self._lock:
            assert copy.device_id not in self._copies, \
                f"data {self.key} already has a copy on device {copy.device_id}"
            self._copies[copy.device_id] = copy
            copy.data = self

    def _detach_copy(self, copy: DataCopy) -> None:
        with self._lock:
            cur = self._copies.get(copy.device_id)
            if cur is copy:
                del self._copies[copy.device_id]

    def get_copy(self, device_id: int) -> Optional[DataCopy]:
        with self._lock:
            return self._copies.get(device_id)

    def copies(self) -> List[DataCopy]:
        with self._lock:
            return list(self._copies.values())

    def newest_version(self) -> int:
        with self._lock:
            return max((c.version for c in self._copies.values()
                        if c.coherency != Coherency.INVALID), default=-1)

    def newest_copy(self, exclude_device: int = -1) -> Optional[DataCopy]:
        """A valid copy holding the newest version (transfer source)."""
        with self._lock:
            best = None
            for c in self._copies.values():
                if c.coherency == Coherency.INVALID or c.device_id == exclude_device:
                    continue
                if best is None or c.version > best.version:
                    best = c
            return best

    # -- coherency protocol -------------------------------------------------
    def start_transfer_ownership(self, device_id: int, access: FlowAccess) -> Optional[DataCopy]:
        """Phase 1 (ref parsec_data_start_transfer_ownership_to_copy,
        parsec/data.c:318): decide whether device_id's copy needs a transfer
        and from where. Returns the source copy to pull from, or None if the
        local copy is already valid.
        """
        with self._lock:
            dst = self._copies.get(device_id)
            assert dst is not None, "transfer ownership to a non-attached copy"
            newest = self.newest_version()
            if dst.coherency != Coherency.INVALID and dst.version == newest:
                return None
            src = self.newest_copy(exclude_device=device_id)
            return src

    def complete_transfer_ownership(self, device_id: int, access: FlowAccess) -> DataCopy:
        """Phase 2: dst copy now holds the newest payload; fix states.

        Write access: dst becomes OWNED, all other copies SHARED (stale-able);
        read access: dst joins the SHARED set (or OWNED copy stays owner).
        """
        with self._lock:
            dst = self._copies[device_id]
            newest = self.newest_version()
            if dst.version < newest:
                dst.version = newest
            if access & FlowAccess.WRITE:
                for c in self._copies.values():
                    if c is not dst and c.coherency != Coherency.INVALID:
                        c.coherency = Coherency.SHARED
                dst.coherency = Coherency.OWNED
                self.owner_device = device_id
            else:
                if dst.coherency == Coherency.INVALID:
                    dst.coherency = Coherency.SHARED
                dst.readers += 1
            return dst

    def version_bump(self, device_id: int) -> int:
        """After a write completes: the writer's copy advances the version
        (ref: CUDA epilog OWNED handback, device_cuda_module.c:2365-2430)."""
        with self._lock:
            dst = self._copies[device_id]
            dst.version = self.newest_version() + 1
            dst.coherency = Coherency.OWNED
            self.owner_device = device_id
            for c in self._copies.values():
                if c is not dst and c.coherency != Coherency.INVALID:
                    c.coherency = Coherency.SHARED
            return dst.version

    def release_reader(self, device_id: int) -> None:
        with self._lock:
            c = self._copies.get(device_id)
            if c is not None and c.readers > 0:
                c.readers -= 1

    def invalidate_others(self, device_id: int) -> None:
        with self._lock:
            for c in self._copies.values():
                if c.device_id != device_id:
                    c.coherency = Coherency.INVALID

    # -- host-side helpers shared by the DSLs -------------------------------
    @staticmethod
    def materialize_host(copy: "DataCopy") -> Any:
        """Ensure ``copy.payload`` is a writable host ndarray and return it.

        A host (device-0) copy can transiently hold an immutable device
        array — e.g. a payload that arrived over the mesh transport's
        device-to-device data plane (comm/mesh.py). Host task bodies
        mutate payloads in place, so the first host consumer materializes
        a writable numpy buffer here; device consumers keep the zero-copy
        device array."""
        import numpy as _np
        p = copy.payload
        if p is not None and not (isinstance(p, _np.ndarray)
                                  and p.flags.writeable):
            copy.payload = _np.array(p)
        return copy.payload

    def host_copy(self) -> DataCopy:
        """The device-0 copy, attached on demand."""
        with self._lock:
            host = self._copies.get(0)
            if host is None:
                host = DataCopy(self, 0, payload=None)
                self._copies[0] = host
            return host

    def sync_to_host(self, devices=None) -> DataCopy:
        """Make the host copy hold the newest version, pulling from the
        owning accelerator if needed. ``devices`` is the context device list
        indexed by device_id (None: direct conversion, no device-module
        stats/LRU bookkeeping)."""
        host = self.host_copy()
        newest = self.newest_copy()
        if newest is not None and newest.device_id != 0 and \
                newest.version > host.version:
            if devices is not None:
                devices[newest.device_id].pull_to_host(self)
                host = self.get_copy(0)
            else:
                import numpy as np
                host.payload = np.array(newest.payload)
                host.version = newest.version
                host.coherency = Coherency.SHARED
        return host

    def _destruct(self) -> None:
        for c in list(self._copies.values()):
            c.data = None
        self._copies.clear()
        super()._destruct()


def data_new_with_payload(payload: Any, device_id: int = 0, key: Any = None) -> Data:
    """Convenience: wrap an existing host array as a Data with one OWNED copy."""
    d = Data(key=key, nb_elts=getattr(payload, "size", 0))
    c = DataCopy(d, device_id, payload=payload)
    c.coherency = Coherency.OWNED
    c.version = 1
    d.attach_copy(c)
    d.owner_device = device_id
    return d
