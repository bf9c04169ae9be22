"""Tiled matrix collections: 2D block-cyclic and friends.

Reference behavior: ``parsec_tiled_matrix_t`` (mtype/storage/mb/nb/lm/ln,
submatrix view i,j,m,n, uplo — ref: parsec/data_dist/matrix/matrix.h:98-125)
with distributions: 2D block-cyclic over a P×Q grid with krows/kcols
cyclicity (ref: parsec/data_dist/matrix/two_dim_rectangle_cyclic.h:73,
grid_2Dcyclic.c), symmetric/triangular storage variant
(sym_two_dim_rectangle_cyclic.c), arbitrary per-tile rank table
(two_dim_tabular.c), and 1-D cyclic vector (vector_two_dim_cyclic.c).

TPU-native notes: tiles are host numpy arrays created lazily; the device
module stages them into HBM on demand. ``to_jax_array`` /
``from_jax_array`` bridge a whole collection to a sharded jax.Array for
interop with mesh-level compute (SURVEY.md §7.1 "interop view").
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from ..data.data import Coherency, Data, data_new_with_payload, to_host
from ..data.datatype import Datatype
from .collection import DataCollection


class TiledMatrix(DataCollection):
    """Base tiled matrix: (mt × nt) tiles of (mb × nb) elements."""

    def __init__(self, lm: int, ln: int, mb: int, nb: int,
                 dtype=np.float32, nodes: int = 1, rank: int = 0,
                 uplo: str = "full") -> None:
        super().__init__(nodes, rank)
        assert uplo in ("full", "lower", "upper")
        self.lm, self.ln = lm, ln
        self.mb, self.nb = mb, nb
        self.mt = (lm + mb - 1) // mb
        self.nt = (ln + nb - 1) // nb
        self.dtype = np.dtype(dtype)
        self.uplo = uplo
        self.dtt = Datatype(self.dtype, (mb, nb))
        self._tiles: Dict[Tuple[int, int], Data] = {}
        self._tlock = threading.Lock()

    # -- tile geometry ------------------------------------------------------
    def tile_shape(self, m: int, n: int) -> Tuple[int, int]:
        """Edge tiles may be partial."""
        tm = self.mb if (m + 1) * self.mb <= self.lm else self.lm - m * self.mb
        tn = self.nb if (n + 1) * self.nb <= self.ln else self.ln - n * self.nb
        return tm, tn

    def tiles(self) -> Iterable[Tuple[int, int]]:
        for m in range(self.mt):
            for n in range(self.nt):
                if self.uplo == "lower" and n > m:
                    continue
                if self.uplo == "upper" and n < m:
                    continue
                yield (m, n)

    def local_tiles(self) -> Iterable[Tuple[int, int]]:
        return (t for t in self.tiles() if self.rank_of(*t) == self.rank)

    # -- DataCollection interface ------------------------------------------
    def data_key(self, m: int, n: int) -> Tuple[int, int]:
        return (m, n)

    def data_of(self, m: int, n: int) -> Data:
        assert 0 <= m < self.mt and 0 <= n < self.nt, f"tile ({m},{n}) out of range"
        if self.uplo == "lower":
            assert n <= m, f"tile ({m},{n}) outside lower storage"
        if self.uplo == "upper":
            assert n >= m, f"tile ({m},{n}) outside upper storage"
        with self._tlock:
            d = self._tiles.get((m, n))
            if d is None:
                payload = np.zeros(self.tile_shape(m, n), dtype=self.dtype)
                d = data_new_with_payload(payload, device_id=0,
                                          key=(id(self), m, n))
                d.collection = self
                d.mesh_coords = (m, n)   # chip placement within a rank's
                self._tiles[(m, n)] = d  # device mesh (mesh_position_of)
            return d

    def mesh_position_of(self, m: int, n: int,
                         grid: Tuple[int, int]) -> Tuple[int, int]:
        """Chip-grid position of tile (m, n) within the owning rank's
        DEVICE MESH (``device_mesh_shape``; ISSUE 6): one level below
        ``rank_of`` — ranks own tiles, chips within a rank's mesh own
        the rank's tiles.  Generic tiled matrices spread tiles
        round-robin over the chip grid."""
        gp, gq = grid
        return (m % gp, n % gq)

    # -- whole-matrix interop ----------------------------------------------
    def set_tile(self, m: int, n: int, values: np.ndarray) -> None:
        d = self.data_of(m, n)
        host = d.get_copy(0)
        if host.payload is None:    # dropped as stale by ``to_numpy``
            host.payload = np.array(values, dtype=self.dtype)
        else:
            np.copyto(host.payload, values, casting="unsafe")
        d.version_bump(0)

    def tile(self, m: int, n: int) -> np.ndarray:
        """Host view of the tile, synced from the newest device copy."""
        return self.data_of(m, n).sync_to_host().payload

    def _snapshot(self, m: int, n: int) -> np.ndarray:
        """The newest version of the tile on the host, to read from: the
        host copy's payload where it is current; where a device holds a
        newer version, that copied out, and the host copy's payload,
        which the newer version made dead bytes, is dropped (``set_tile``
        or a sync makes a new one): the caller's array is then the one
        host copy of the tile, not the second."""
        d = self.data_of(m, n)
        with d._lock:
            host, newest = d.host_copy(), d.newest_copy()
            if newest is None or newest.device_id == 0 \
                    or newest.version <= host.version:
                return host.payload
            host.payload = None
            host.coherency = Coherency.INVALID
            arr = newest.payload
        return to_host(arr)

    def to_numpy(self) -> np.ndarray:
        """Assemble the full (local) matrix; missing symmetric tiles are
        mirrored when uplo != full.  A read: a tile whose newest version
        is on a device is copied from there and NOT kept on the host as
        well (``_snapshot``)."""
        out = np.zeros((self.lm, self.ln), dtype=self.dtype)
        for m in range(self.mt):
            for n in range(self.nt):
                sm, sn = m * self.mb, n * self.nb
                tm, tn = self.tile_shape(m, n)
                if self.uplo == "lower" and n > m:
                    out[sm:sm + tm, sn:sn + tn] = self.tile(n, m).T[:tm, :tn]
                    continue
                if self.uplo == "upper" and n < m:
                    out[sm:sm + tm, sn:sn + tn] = self.tile(n, m).T[:tm, :tn]
                    continue
                out[sm:sm + tm, sn:sn + tn] = self._snapshot(m, n)
        return out

    def from_numpy(self, a: np.ndarray) -> "TiledMatrix":
        assert a.shape == (self.lm, self.ln)
        for (m, n) in self.tiles():
            sm, sn = m * self.mb, n * self.nb
            tm, tn = self.tile_shape(m, n)
            # one pass: the copy into the tile's buffer casts as it goes
            self.set_tile(m, n, a[sm:sm + tm, sn:sn + tn])
        return self

    def to_jax_array(self, device=None):
        """Interop: materialize as one jax array (host assembles)."""
        import jax
        return jax.device_put(self.to_numpy(), device)


class TwoDimBlockCyclic(TiledMatrix):
    """P×Q block-cyclic with k-cyclicity
    (ref: parsec_matrix_block_cyclic_t, two_dim_rectangle_cyclic.h:73)."""

    def __init__(self, lm: int, ln: int, mb: int, nb: int,
                 P: int = 1, Q: int = 1, krows: int = 1, kcols: int = 1,
                 dtype=np.float32, nodes: Optional[int] = None, rank: int = 0,
                 uplo: str = "full") -> None:
        nodes = nodes if nodes is not None else P * Q
        assert P * Q <= nodes, f"grid {P}x{Q} needs {P*Q} ranks, have {nodes}"
        super().__init__(lm, ln, mb, nb, dtype, nodes, rank, uplo)
        self.P, self.Q = P, Q
        self.krows, self.kcols = krows, kcols

    def rank_of(self, m: int, n: int) -> int:
        pr = (m // self.krows) % self.P
        pc = (n // self.kcols) % self.Q
        return pr * self.Q + pc

    def mesh_position_of(self, m: int, n: int,
                         grid: Tuple[int, int]) -> Tuple[int, int]:
        """Block-cyclic over the chip grid in LOCAL block coordinates:
        a rank owns every P-th block row (Q-th block column), so
        dividing by the rank grid first makes the rank's consecutive
        local tiles land on consecutive chips — the same distribution
        ``rank_of`` applies one level up.  The effective executor grid
        is therefore (P*gp) x (Q*gq) without any rank seeing a foreign
        tile."""
        gp, gq = grid
        return ((m // self.krows // self.P) % gp,
                (n // self.kcols // self.Q) % gq)

    def vpid_of(self, m: int, n: int) -> int:
        return 0


class BlockColumnCyclic(TwoDimBlockCyclic):
    """Block columns on one rank: the distribution DPLASMA's
    ``dgetrf_1d`` asks for (``mb = M``, tiles of ``M x nb``).  Tile
    (0, n) is the whole block column n, so a task that owns a tile owns
    every row of it and a pivot search or a row interchange stays
    inside one task.

    Takes :class:`TwoDimBlockCyclic`'s leading arguments so that code
    which tiles by ``(lm, ln, mb, nb)`` can be handed either; the tile
    height is ``lm`` whatever ``mb`` says.
    """

    def __init__(self, lm: int, ln: int, mb: int, nb: int,
                 dtype=np.float32) -> None:
        super().__init__(lm, ln, lm, nb, dtype=dtype)


class SymTwoDimBlockCyclic(TwoDimBlockCyclic):
    """Triangular/symmetric storage block-cyclic
    (ref: sym_two_dim_rectangle_cyclic.c)."""

    def __init__(self, lm: int, ln: int, mb: int, nb: int, uplo: str = "lower",
                 **kw) -> None:
        assert uplo in ("lower", "upper")
        super().__init__(lm, ln, mb, nb, uplo=uplo, **kw)


class TwoDimBlockCyclicBand(TwoDimBlockCyclic):
    """Band distribution: tiles within the band are distributed block-
    cyclically; out-of-band tiles have no storage
    (ref: two_dim_rectangle_cyclic_band.c)."""

    def __init__(self, lm: int, ln: int, mb: int, nb: int, band_size: int,
                 **kw) -> None:
        super().__init__(lm, ln, mb, nb, **kw)
        self.band_size = band_size

    def in_band(self, m: int, n: int) -> bool:
        return abs(m - n) < self.band_size

    def tiles(self):
        for (m, n) in super().tiles():
            if self.in_band(m, n):
                yield (m, n)

    def data_of(self, m: int, n: int) -> Data:
        assert self.in_band(m, n), f"tile ({m},{n}) outside band"
        return super().data_of(m, n)


class SymTwoDimBlockCyclicBand(TwoDimBlockCyclicBand):
    """Band + triangular storage: only in-band tiles on the stored side
    (ref: sym_two_dim_rectangle_cyclic_band.c)."""

    def __init__(self, lm: int, ln: int, mb: int, nb: int, band_size: int,
                 uplo: str = "lower", **kw) -> None:
        assert uplo in ("lower", "upper")
        super().__init__(lm, ln, mb, nb, band_size, uplo=uplo, **kw)


class TwoDimTabular(TiledMatrix):
    """Arbitrary per-tile rank table (ref: two_dim_tabular.c)."""

    def __init__(self, lm: int, ln: int, mb: int, nb: int,
                 rank_table: np.ndarray, **kw) -> None:
        super().__init__(lm, ln, mb, nb, **kw)
        rank_table = np.asarray(rank_table)
        assert rank_table.shape == (self.mt, self.nt), \
            f"rank table {rank_table.shape} != tile grid {(self.mt, self.nt)}"
        self.rank_table = rank_table

    def rank_of(self, m: int, n: int) -> int:
        return int(self.rank_table[m, n])

    @staticmethod
    def random(lm, ln, mb, nb, nodes: int, seed: int = 0, **kw) -> "TwoDimTabular":
        mt, nt = (lm + mb - 1) // mb, (ln + nb - 1) // nb
        rng = np.random.RandomState(seed)
        table = rng.randint(0, nodes, size=(mt, nt))
        return TwoDimTabular(lm, ln, mb, nb, table, nodes=nodes, **kw)


class VectorTwoDimCyclic(TiledMatrix):
    """1-D cyclic vector of segments (ref: vector_two_dim_cyclic.c)."""

    def __init__(self, lm: int, mb: int, P: int = 1, dtype=np.float32,
                 nodes: Optional[int] = None, rank: int = 0) -> None:
        nodes = nodes if nodes is not None else P
        super().__init__(lm, 1, mb, 1, dtype, nodes, rank)
        self.P = P

    def rank_of(self, m: int, n: int = 0) -> int:
        return m % self.P

    def data_of(self, m: int, n: int = 0) -> Data:
        return super().data_of(m, 0)
