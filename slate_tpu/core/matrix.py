"""Distributed matrix class hierarchy.

TPU-native analog of the reference's 12-class matrix layer
(ref: include/slate/BaseMatrix.hh:39-738 and Matrix.hh / BaseTrapezoidMatrix.hh
/ TriangularMatrix.hh / SymmetricMatrix.hh / HermitianMatrix.hh /
BaseBandMatrix.hh / BandMatrix.hh / TriangularBandMatrix.hh /
HermitianBandMatrix.hh).

Differences forced (for the better) by the TPU programming model:

- Matrices are **immutable pytrees**.  Reference routines mutate tiles in
  place under MOSI coherency; here every driver returns new matrices whose
  storage is a new SSA value.  XLA's buffer donation recovers in-place update
  performance without aliasing hazards.
- ``sub``/``transpose``/``conj_transpose`` are metadata-only views sharing the
  parent's storage object (zero-copy, ref: BaseMatrix.hh:941-1122 sub/slice,
  Tile.hh:40-90 transpose views); materialisation happens lazily inside jit
  where XLA fuses the gather/transpose into consumers.
- Tile coherency API (tileGetForReading/Writing, BaseMatrix.hh:2968-3396) has
  no analog: there is one copy of every tile, owned by its mesh coordinate.
- The communication API (tileBcast/listBcast/listReduce,
  BaseMatrix.hh:451-477) lives in slate_tpu.comm as mesh collectives.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..exceptions import slate_error
from ..types import Diag, Op, TileKind, Uplo, compose_op, is_complex
from ..util.trace import span
from . import layout
from .grid import Grid
from .storage import TileStorage

__all__ = [
    "BaseMatrix", "Matrix", "BaseTrapezoidMatrix", "TrapezoidMatrix",
    "TriangularMatrix", "SymmetricMatrix", "HermitianMatrix",
    "BaseBandMatrix", "BandMatrix", "TriangularBandMatrix",
    "HermitianBandMatrix",
]


@jax.tree_util.register_pytree_node_class
class BaseMatrix:
    """Shared base: storage + (tile-offset, extent, op) view metadata.

    View coordinates (io, jo, mt, nt) index the *storage* tile grid; ``op``
    transposes on top, applied in accessors — mirroring how the reference
    routes every index through ``op()`` (BaseMatrix.hh:4048-4088).
    """

    uplo: Uplo = Uplo.General
    diag: Diag = Diag.NonUnit

    def __init__(self, storage: TileStorage, io: int = 0, jo: int = 0,
                 mt: Optional[int] = None, nt: Optional[int] = None,
                 op: Op = Op.NoTrans, kind: TileKind = TileKind.SlateOwned):
        self.storage = storage
        self.io, self.jo = int(io), int(jo)
        self._mt = storage.Mt - self.io if mt is None else int(mt)
        self._nt = storage.Nt - self.jo if nt is None else int(nt)
        self.op = op
        self.kind = kind
        slate_error(0 <= self.io and self.io + self._mt <= storage.Mt and
                    0 <= self.jo and self.jo + self._nt <= storage.Nt,
                    "view out of range")

    # ---- pytree ----
    def tree_flatten(self):
        aux = (self.io, self.jo, self._mt, self._nt, self.op, self.kind,
               self._extra_aux())
        return (self.storage,), aux

    def _extra_aux(self):
        return ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        io, jo, mt, nt, op, kind, extra = aux
        obj = cls.__new__(cls)
        BaseMatrix.__init__(obj, children[0], io, jo, mt, nt, op, kind)
        obj._apply_extra_aux(extra)
        return obj

    def _apply_extra_aux(self, extra):
        pass

    # ---- shape accessors (op-aware) ----
    @property
    def grid(self) -> Grid:
        return self.storage.grid

    @property
    def dtype(self):
        return self.storage.dtype

    def _m_store(self) -> int:
        st = self.storage
        if self._mt == 0:
            return 0
        last = self.io + self._mt - 1
        return (self._mt - 1) * st.mb + st.tile_mb(last)

    def _n_store(self) -> int:
        st = self.storage
        if self._nt == 0:
            return 0
        last = self.jo + self._nt - 1
        return (self._nt - 1) * st.nb + st.tile_nb(last)

    @property
    def m(self) -> int:
        return self._m_store() if self.op is Op.NoTrans else self._n_store()

    @property
    def n(self) -> int:
        return self._n_store() if self.op is Op.NoTrans else self._m_store()

    @property
    def mt(self) -> int:
        return self._mt if self.op is Op.NoTrans else self._nt

    @property
    def nt(self) -> int:
        return self._nt if self.op is Op.NoTrans else self._mt

    @property
    def mb(self) -> int:
        return self.storage.mb if self.op is Op.NoTrans else self.storage.nb

    @property
    def nb(self) -> int:
        return self.storage.nb if self.op is Op.NoTrans else self.storage.mb

    def tile_mb(self, i: int) -> int:
        if self.op is Op.NoTrans:
            return min(self.storage.tile_mb(self.io + i), self._m_store() - i * self.mb)
        return min(self.storage.tile_nb(self.jo + i), self._n_store() - i * self.mb)

    def tile_nb(self, j: int) -> int:
        if self.op is Op.NoTrans:
            return min(self.storage.tile_nb(self.jo + j), self._n_store() - j * self.nb)
        return min(self.storage.tile_mb(self.io + j), self._m_store() - j * self.nb)

    def tile_rank(self, i: int, j: int) -> int:
        if self.op is not Op.NoTrans:
            i, j = j, i
        return self.storage.tile_rank(self.io + i, self.jo + j)

    # ---- views (zero-copy: share self.storage) ----
    def sub(self, i1: int, i2: int, j1: int, j2: int):
        """Tile-index submatrix view, inclusive ranges like the reference
        (ref: BaseMatrix.hh:941-1122).  Returns a general Matrix view."""
        if self.op is not Op.NoTrans:
            i1, i2, j1, j2 = j1, j2, i1, i2
        mt = max(0, i2 - i1 + 1)
        nt = max(0, j2 - j1 + 1)
        v = Matrix.__new__(Matrix)
        BaseMatrix.__init__(v, self.storage, self.io + i1, self.jo + j1,
                            mt, nt, self.op, self.kind)
        return v

    def transpose(self):
        v = self.__class__.__new__(self.__class__)
        BaseMatrix.__init__(v, self.storage, self.io, self.jo, self._mt,
                            self._nt, compose_op(self.op, Op.Trans), self.kind)
        v._apply_extra_aux(self._extra_aux())
        return v

    def conj_transpose(self):
        if not is_complex(self.dtype):
            return self.transpose()
        v = self.__class__.__new__(self.__class__)
        BaseMatrix.__init__(v, self.storage, self.io, self.jo, self._mt,
                            self._nt, compose_op(self.op, Op.ConjTrans),
                            self.kind)
        v._apply_extra_aux(self._extra_aux())
        return v

    @property
    def T(self):
        return self.transpose()

    @property
    def H(self):
        return self.conj_transpose()

    def is_root_view(self) -> bool:
        return (self.io == 0 and self.jo == 0 and
                self._mt == self.storage.Mt and self._nt == self.storage.Nt)

    # ---- materialisation ----
    def _dense_store(self):
        """Dense [m, n] of the untransposed view region."""
        st = self.storage
        if self.is_root_view():
            return st.to_dense()
        with span("slate.layout/to_dense"):
            tiles = st.canonical()[self.io:self.io + self._mt,
                                   self.jo:self.jo + self._nt]
            return layout.untile_dense(tiles, self._m_store(),
                                       self._n_store())

    def to_dense(self):
        """Materialise as a plain [m, n] jnp array (op applied, structure
        expanded — symmetric/triangular/band subclasses override _expand)."""
        with span("slate.layout/to_dense"):
            d = self._expand(self._dense_store())
            if self.op is Op.Trans:
                d = d.T
            elif self.op is Op.ConjTrans:
                d = d.conj().T
            return d

    def _expand(self, dense):
        return dense

    def to_numpy(self) -> np.ndarray:
        # root general views export through the NATIVE tile unpacker when
        # built (one host pass over the fetched tile array); structured
        # types and op views need to_dense()'s expansion.  Check the
        # library and dtype BEFORE fetching — a failed attempt would have
        # paid the full device->host transfer twice
        if (type(self) is Matrix and self.op is Op.NoTrans
                and self.is_root_view()):
            from .. import native as _native
            st = self.storage
            if _native.available() and _native.supports(st.dtype):
                tiles = np.asarray(jax.device_get(st.data))
                out = _native.unpack_tiles(tiles, st.m, st.n, st.grid.p,
                                           st.grid.q)
                if out is not None:
                    return out
        return np.asarray(jax.device_get(self.to_dense()))

    def with_dense(self, dense):
        """Return a same-view matrix whose view region holds ``dense``
        (functional write-back; parent storage regions outside the view are
        preserved)."""
        if self.op is Op.Trans:
            dense = dense.T
        elif self.op is Op.ConjTrans:
            dense = jnp.conj(dense).T
        st = self.storage
        if self.is_root_view():
            new_st = st.with_dense(dense)
        else:
            with span("slate.layout/from_dense"):
                tiles = st.canonical()
                sub = layout.tile_dense(dense, st.mb, st.nb)
                tiles = jax.lax.dynamic_update_slice(
                    tiles, sub.astype(tiles.dtype), (self.io, self.jo, 0, 0))
                new_st = st.with_canonical(tiles)
        v = self.__class__.__new__(self.__class__)
        BaseMatrix.__init__(v, new_st, self.io, self.jo, self._mt, self._nt,
                            self.op, self.kind)
        v._apply_extra_aux(self._extra_aux())
        return v

    def emptyLike(self, dtype=None):
        """Same shape/distribution, zero data (ref: Matrix::emptyLike)."""
        st = self.storage
        z = TileStorage.zeros(st.m, st.n, st.mb, st.nb, st.grid,
                              dtype or st.dtype)
        v = self.__class__.__new__(self.__class__)
        BaseMatrix.__init__(v, z, self.io, self.jo, self._mt, self._nt,
                            self.op, self.kind)
        v._apply_extra_aux(self._extra_aux())
        return v

    def __repr__(self):
        extra = "" if self.op is Op.NoTrans else f", op={self.op.name}"
        return (f"{self.__class__.__name__}({self.m}x{self.n}, "
                f"tiles {self.mb}x{self.nb}, grid {self.grid.p}x"
                f"{self.grid.q}{extra})")


@jax.tree_util.register_pytree_node_class
class Matrix(BaseMatrix):
    """General m*n matrix (ref: include/slate/Matrix.hh:58-163)."""

    @classmethod
    def zeros(cls, m, n, mb, nb=None, grid=None, dtype=jnp.float32):
        nb = nb or mb
        return cls(TileStorage.zeros(m, n, mb, nb, grid or Grid(1, 1), dtype))

    @classmethod
    def from_numpy(cls, a, mb, nb=None, grid=None, kind=TileKind.UserOwned):
        """Import user data (ref: fromLAPACK, Matrix.hh:58-112).

        Host numpy arrays are passed through UNconverted so from_dense can
        take the native one-pass tile packer; jnp.asarray here would hide
        the numpy-ness and silently fall back to the device layout ops."""
        nb = nb or mb
        a = a if isinstance(a, np.ndarray) else jnp.asarray(a)
        st = TileStorage.from_dense(a, mb, nb, grid or Grid(1, 1))
        return cls(st, kind=kind)

    # ---- structure reinterpretation (ref: conversion ctors) ----
    def triangular(self, uplo: Uplo, diag: Diag = Diag.NonUnit):
        slate_error(self.m == self.n, "triangular view needs square")
        return TriangularMatrix._from_view(self, uplo, diag)

    def symmetric(self, uplo: Uplo):
        slate_error(self.m == self.n, "symmetric view needs square")
        return SymmetricMatrix._from_view(self, uplo)

    def hermitian(self, uplo: Uplo):
        slate_error(self.m == self.n, "hermitian view needs square")
        return HermitianMatrix._from_view(self, uplo)

    def trapezoid(self, uplo: Uplo, diag: Diag = Diag.NonUnit):
        return TrapezoidMatrix._from_view(self, uplo, diag)


@jax.tree_util.register_pytree_node_class
class BaseTrapezoidMatrix(BaseMatrix):
    """Upper/lower trapezoid storage base
    (ref: include/slate/BaseTrapezoidMatrix.hh)."""

    def __init__(self, storage, uplo: Uplo = Uplo.Lower,
                 diag: Diag = Diag.NonUnit, **kw):
        super().__init__(storage, **kw)
        self.uplo = uplo
        self.diag = diag

    def _extra_aux(self):
        return (self.uplo, self.diag)

    def _apply_extra_aux(self, extra):
        self.uplo, self.diag = extra

    @classmethod
    def _from_view(cls, src: BaseMatrix, uplo: Uplo, diag: Diag = Diag.NonUnit):
        v = cls.__new__(cls)
        BaseMatrix.__init__(v, src.storage, src.io, src.jo, src._mt, src._nt,
                            src.op, src.kind)
        # A lower view of a transposed matrix is an upper view of storage.
        if src.op is not Op.NoTrans:
            uplo = Uplo.Upper if uplo is Uplo.Lower else Uplo.Lower
        v._apply_extra_aux((uplo, diag))
        return v

    def _uplo_logical(self) -> Uplo:
        """uplo as seen through op (ref: BaseMatrix::uploLogical)."""
        if self.op is Op.NoTrans:
            return self.uplo
        return Uplo.Upper if self.uplo is Uplo.Lower else Uplo.Lower

    def _expand(self, dense):
        m, n = dense.shape
        i = jnp.arange(m)[:, None]
        j = jnp.arange(n)[None, :]
        mask = (i >= j) if self.uplo is Uplo.Lower else (i <= j)
        d = jnp.where(mask, dense, jnp.zeros((), dense.dtype))
        if self.diag is Diag.Unit:
            k = min(m, n)
            d = d.at[jnp.arange(k), jnp.arange(k)].set(1)
        return d

    def general(self) -> Matrix:
        """Expand to a general Matrix (materialises the structure)."""
        g = Matrix.zeros(self.m, self.n, self.mb, self.nb, self.grid,
                         self.dtype)
        return g.with_dense(self.to_dense())


@jax.tree_util.register_pytree_node_class
class TrapezoidMatrix(BaseTrapezoidMatrix):
    """ref: include/slate/TrapezoidMatrix.hh"""


@jax.tree_util.register_pytree_node_class
class TriangularMatrix(BaseTrapezoidMatrix):
    """ref: include/slate/TriangularMatrix.hh"""

    @classmethod
    def from_numpy(cls, a, mb, uplo=Uplo.Lower, diag=Diag.NonUnit, grid=None):
        return cls._from_view(Matrix.from_numpy(a, mb, mb, grid), uplo, diag)


@jax.tree_util.register_pytree_node_class
class SymmetricMatrix(BaseTrapezoidMatrix):
    """ref: include/slate/SymmetricMatrix.hh — only the uplo triangle is
    referenced; _expand mirrors it."""

    @classmethod
    def from_numpy(cls, a, mb, uplo=Uplo.Lower, grid=None):
        return cls._from_view(Matrix.from_numpy(a, mb, mb, grid), uplo)

    def _expand(self, dense):
        tri = BaseTrapezoidMatrix._expand(self, dense)
        d = jnp.diagonal(tri)
        return tri + tri.T - jnp.diag(d)


@jax.tree_util.register_pytree_node_class
class HermitianMatrix(BaseTrapezoidMatrix):
    """ref: include/slate/HermitianMatrix.hh"""

    @classmethod
    def from_numpy(cls, a, mb, uplo=Uplo.Lower, grid=None):
        return cls._from_view(Matrix.from_numpy(a, mb, mb, grid), uplo)

    def _expand(self, dense):
        tri = BaseTrapezoidMatrix._expand(self, dense)
        d = jnp.real(jnp.diagonal(tri))
        full = tri + jnp.conj(tri).T
        k = min(full.shape)
        return full.at[jnp.arange(k), jnp.arange(k)].set(
            d.astype(full.dtype))


@jax.tree_util.register_pytree_node_class
class BaseBandMatrix(BaseMatrix):
    """Band storage base (ref: include/slate/BaseBandMatrix.hh).

    The band is kept inside the same blocked layout; tiles wholly outside the
    band are structural zeros (the pad invariant covers them), matching the
    reference's choice to simply not insert out-of-band tiles."""

    def __init__(self, storage, kl: int = 0, ku: int = 0, **kw):
        super().__init__(storage, **kw)
        self.kl, self.ku = int(kl), int(ku)

    def _extra_aux(self):
        return (self.kl, self.ku)

    def _apply_extra_aux(self, extra):
        self.kl, self.ku = extra

    def _expand(self, dense):
        m, n = dense.shape
        i = jnp.arange(m)[:, None]
        j = jnp.arange(n)[None, :]
        mask = (j - i <= self.ku) & (i - j <= self.kl)
        return jnp.where(mask, dense, jnp.zeros((), dense.dtype))


@jax.tree_util.register_pytree_node_class
class BandMatrix(BaseBandMatrix):
    """General band (ref: include/slate/BandMatrix.hh)."""

    @classmethod
    def from_numpy(cls, a, kl, ku, mb, grid=None):
        a = a if isinstance(a, np.ndarray) else jnp.asarray(a)
        st = TileStorage.from_dense(a, mb, mb, grid or Grid(1, 1))
        return cls(st, kl=kl, ku=ku)


@jax.tree_util.register_pytree_node_class
class TriangularBandMatrix(BaseBandMatrix):
    """ref: include/slate/TriangularBandMatrix.hh"""

    @classmethod
    def from_numpy(cls, a, kd, mb, uplo: Uplo = Uplo.Lower,
                   diag: Diag = Diag.NonUnit, grid=None):
        st = TileStorage.from_dense(jnp.asarray(a), mb, mb,
                                    grid or Grid(1, 1))
        return cls(st, kd=kd, uplo=uplo, diag=diag)

    def __init__(self, storage, kd: int = 0, uplo: Uplo = Uplo.Lower,
                 diag: Diag = Diag.NonUnit, **kw):
        kl, ku = (kd, 0) if uplo is Uplo.Lower else (0, kd)
        super().__init__(storage, kl=kl, ku=ku, **kw)
        self.uplo, self.diag, self.kd = uplo, diag, int(kd)

    def _extra_aux(self):
        return (self.kd, self.uplo, self.diag)

    def _apply_extra_aux(self, extra):
        self.kd, self.uplo, self.diag = extra
        self.kl, self.ku = (self.kd, 0) if self.uplo is Uplo.Lower \
            else (0, self.kd)

    def _expand(self, dense):
        band = BaseBandMatrix._expand(self, dense)
        if self.diag is Diag.Unit:
            k = min(dense.shape)
            band = band.at[jnp.arange(k), jnp.arange(k)].set(1)
        return band


@jax.tree_util.register_pytree_node_class
class HermitianBandMatrix(BaseBandMatrix):
    """ref: include/slate/HermitianBandMatrix.hh"""

    @classmethod
    def from_numpy(cls, a, kd, mb, uplo: Uplo = Uplo.Lower, grid=None):
        st = TileStorage.from_dense(jnp.asarray(a), mb, mb,
                                    grid or Grid(1, 1))
        return cls(st, kd=kd, uplo=uplo)

    def __init__(self, storage, kd: int = 0, uplo: Uplo = Uplo.Lower, **kw):
        kl, ku = (kd, 0) if uplo is Uplo.Lower else (0, kd)
        super().__init__(storage, kl=kl, ku=ku, **kw)
        self.uplo, self.kd = uplo, int(kd)

    def _extra_aux(self):
        return (self.kd, self.uplo)

    def _apply_extra_aux(self, extra):
        self.kd, self.uplo = extra
        self.kl, self.ku = (self.kd, 0) if self.uplo is Uplo.Lower \
            else (0, self.kd)

    def _expand(self, dense):
        band = BaseBandMatrix._expand(self, dense)
        d = jnp.real(jnp.diagonal(band)) if is_complex(self.dtype) \
            else jnp.diagonal(band)
        full = band + jnp.conj(band).T
        k = min(full.shape)
        return full.at[jnp.arange(k), jnp.arange(k)].set(d.astype(full.dtype))
