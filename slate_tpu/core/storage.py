"""TileStorage: the distributed tile map as one sharded blocked array.

TPU-native analog of the reference's storage & coherency layer
(ref: include/slate/internal/MatrixStorage.hh:284-529 `MatrixStorage`,
Memory.hh:29-95 block pool, MatrixStorage.hh:33-148 MOSI states):

- The reference keeps a hash map {(i, j) -> TileNode} with one TileInstance
  per device plus MOSI (Modified/Shared/Invalid/OnHold) coherency because a
  tile may be replicated across host + several GPUs.  On TPU there is a single
  memory space per chip and XLA owns buffer lifetimes, so the map becomes ONE
  dense blocked array ``[p*mtl, q*ntl, mb, nb]`` in cyclic order, sharded over
  the mesh so device (r, c) holds exactly its 2D-block-cyclic tiles
  ``{(i, j) : i ≡ r (mod p), j ≡ c (mod q)}`` in HBM.  MOSI is unnecessary:
  functional arrays cannot alias-stale, which is the whole problem MOSI solves.
- The reference's `Memory` pool (per-device stacks of mb*nb blocks) maps to
  XLA's arena allocator: tiles of one matrix are a single contiguous HBM
  buffer, the strongest form of pooling.  Workspace "life" counters
  (MatrixStorage.hh:1274-1283 tileTick) map to SSA value lifetimes inside the
  compiled program — a broadcast panel dies when its last consumer retires,
  which XLA computes exactly rather than by reference counting.
- tileMb/tileNb/tileRank/tileDevice distribution lambdas
  (MatrixStorage.hh:533-586) are `tile_mb`/`tile_nb` here plus Grid's maps.

Storage is a registered pytree so matrices flow through jit/shard_map.
"""

from __future__ import annotations

import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..exceptions import slate_error
from ..util.trace import span
from . import layout
from .grid import Grid


@jax.tree_util.register_pytree_node_class
class TileStorage:
    """Tiles of an m*n matrix in 2D block-cyclic order on a p*q grid.

    data[s, t] holds tile (i, j) with i = (s % mtl)*p + s//mtl,
    j = (t % ntl)*q + t//ntl; rows s // mtl == r live on mesh row r.
    """

    def __init__(self, data, m: int, n: int, mb: int, nb: int, grid: Grid):
        self.data = data
        self.m, self.n = int(m), int(n)
        self.mb, self.nb = int(mb), int(nb)
        self.grid = grid
        self.Mt = layout.num_tiles(self.m, self.mb)
        self.Nt = layout.num_tiles(self.n, self.nb)
        self.mtl = -(-self.Mt // grid.p)
        self.ntl = -(-self.Nt // grid.q)

    # ---- pytree ----
    def tree_flatten(self):
        return (self.data,), (self.m, self.n, self.mb, self.nb, self.grid)

    @classmethod
    def tree_unflatten(cls, aux, children):
        m, n, mb, nb, grid = aux
        return cls(children[0], m, n, mb, nb, grid)

    # ---- constructors ----
    @classmethod
    def zeros(cls, m, n, mb, nb, grid: Grid | None = None, dtype=jnp.float32):
        grid = grid or Grid(1, 1)
        Mt, Nt = layout.num_tiles(m, mb), layout.num_tiles(n, nb)
        mtl, ntl = -(-Mt // grid.p), -(-Nt // grid.q)
        data = jnp.zeros((grid.p * mtl, grid.q * ntl, mb, nb), dtype)
        st = cls(data, m, n, mb, nb, grid)
        return st._shard()

    @classmethod
    def from_dense(cls, dense, mb, nb, grid: Grid | None = None):
        """Import a host/global array (ref: Matrix::fromLAPACK, Matrix.hh:344).

        Host numpy f32/f64 inputs go through the NATIVE tile packer
        (native/slate_tpu_native.cc, OpenMP across tiles) when built —
        one memory-bandwidth pass instead of a device reshape/transpose
        chain; traced/device inputs use the jnp layout ops."""
        grid = grid or Grid(1, 1)
        if isinstance(dense, np.ndarray) and dense.ndim == 2:
            from .. import native as _native
            packed = _native.pack_tiles(dense, mb, nb, grid.p, grid.q)
            if packed is not None:
                st = cls(jnp.asarray(packed), dense.shape[0],
                         dense.shape[1], mb, nb, grid)
                return st._shard()
        with span("slate.layout/from_dense"):
            dense = jnp.asarray(dense)
            slate_error(dense.ndim == 2, "from_dense needs a 2D array")
            tiles = layout.tile_dense(dense, mb, nb)
            data = layout.canonical_to_cyclic(tiles, grid.p, grid.q)
        st = cls(data, dense.shape[0], dense.shape[1], mb, nb, grid)
        return st._shard()

    @classmethod
    def from_canonical(cls, tiles, m, n, grid: Grid | None = None):
        grid = grid or Grid(1, 1)
        Mt, Nt, mb, nb = tiles.shape
        slate_error(Mt == layout.num_tiles(m, mb) and
                    Nt == layout.num_tiles(n, nb), "tile grid mismatch")
        with span("slate.layout/from_dense"):
            data = layout.canonical_to_cyclic(tiles, grid.p, grid.q)
        st = cls(data, m, n, mb, nb, grid)
        return st._shard()

    def _shard(self) -> "TileStorage":
        sh = self.grid.tile_sharding()
        if sh is not None:
            try:
                self.data = jax.device_put(self.data, sh)
            except (AssertionError, ValueError):
                # an eager compute result can carry a GSPMD (non-Named)
                # sharding, and jax's different-device-order reshard
                # path only accepts NamedSharding inputs; bounce through
                # host for that cross-mesh corner (redistribute between
                # permuted grids) — only reachable eagerly
                self.data = jax.device_put(jax.device_get(self.data), sh)
        return self

    # ---- distribution lambdas (ref: MatrixStorage.hh:533-586) ----
    def tile_mb(self, i: int) -> int:
        """Rows in tile-row i (last tile may be partial)."""
        return self.mb if i < self.Mt - 1 else self.m - (self.Mt - 1) * self.mb

    def tile_nb(self, j: int) -> int:
        return self.nb if j < self.Nt - 1 else self.n - (self.Nt - 1) * self.nb

    def tile_rank(self, i: int, j: int) -> int:
        return self.grid.tile_rank(i, j)

    def tile_device(self, i: int, j: int):
        return self.grid.tile_device(i, j)

    # ---- views of the store ----
    def canonical(self):
        """Tiles in natural (i, j) order: [Mt, Nt, mb, nb]."""
        with span("slate.layout/to_dense"):
            return layout.cyclic_to_canonical(
                self.data, self.Mt, self.Nt, self.grid.p, self.grid.q)

    def to_dense(self):
        with span("slate.layout/to_dense"):
            return layout.untile_dense(self.canonical(), self.m, self.n)

    def with_canonical(self, tiles) -> "TileStorage":
        with span("slate.layout/from_dense"):
            data = layout.canonical_to_cyclic(tiles, self.grid.p,
                                              self.grid.q)
        st = TileStorage(data, self.m, self.n, self.mb, self.nb, self.grid)
        return st._shard()

    def with_dense(self, dense) -> "TileStorage":
        return TileStorage.from_dense(dense, self.mb, self.nb, self.grid)

    def tile(self, i: int, j: int):
        """Fetch one tile (debug/test path; ref: BaseMatrix::at)."""
        ci, _, _ = layout.cyclic_row_maps(self.Mt, self.grid.p)
        cj, _, _ = layout.cyclic_row_maps(self.Nt, self.grid.q)
        return self.data[int(ci[i]), int(cj[j])]

    def set_tile(self, i: int, j: int, tile) -> "TileStorage":
        ci, _, _ = layout.cyclic_row_maps(self.Mt, self.grid.p)
        cj, _, _ = layout.cyclic_row_maps(self.Nt, self.grid.q)
        data = self.data.at[int(ci[i]), int(cj[j])].set(tile)
        return TileStorage(data, self.m, self.n, self.mb, self.nb, self.grid)

    @property
    def dtype(self):
        return self.data.dtype

    def astype(self, dtype) -> "TileStorage":
        """Precision-converting copy (ref: copy / gecopy convert path)."""
        return TileStorage(self.data.astype(dtype), self.m, self.n,
                           self.mb, self.nb, self.grid)

    def __repr__(self):
        return (f"TileStorage({self.m}x{self.n}, tiles {self.mb}x{self.nb}, "
                f"grid {self.grid.p}x{self.grid.q}, {self.dtype})")


# residency codes for TileMap._res
_RES_HOST = 0    # host bytes authoritative, no device copy
_RES_DEVICE = 1  # clean copy staged on device (prefetch in flight or held)
_RES_DIRTY = 2   # device bytes newer than host (writeback pending)

_RES_NAMES = {_RES_HOST: "host", _RES_DEVICE: "device", _RES_DIRTY: "dirty"}


class TileMap:
    """Host-resident tile map with per-tile residency for out-of-core work.

    The explicit analog of the reference's ``MatrixStorage`` tile map with
    host/device coherency (ref: include/slate/internal/MatrixStorage.hh
    MOSI states, PAPER L3/L4): where ``TileStorage`` above collapses the
    map into one HBM-resident sharded array (fine when the matrix fits),
    ``TileMap`` keeps the authoritative bytes in host RAM and streams
    panel-shaped windows to the device on demand, so ``getrf``/``potrf``
    run at n beyond device memory.  The three-state residency ledger is
    the MOSI subset that matters on a single-memory-space accelerator:

    - ``host``    host bytes authoritative, nothing staged,
    - ``device``  a clean copy staged in HBM (``prefetch`` issued),
    - ``dirty``   device bytes newer than host (``store`` writeback
      pending until :meth:`drain`).

    Copies are ASYNC on both axes — ``jax.device_put`` for H2D and
    ``copy_to_host_async`` for D2H — so the OOC loops overlap the next
    panel's transfer against the current panel's update, the PR 15
    hide-communication discipline applied to the host-device axis.
    Double-buffer protocol: ``prefetch(region)`` stages the next window
    while compute runs; ``fetch(region)`` consumes (pops) the staged
    buffer or falls back to a synchronous-dispatch H2D on a miss;
    ``store(region, arr)`` queues an async writeback.  ``drain`` (called
    automatically by the first fetch after a store, and explicitly before
    a checkpoint snapshot) lands pending writebacks into host RAM.

    Thread safety: the residency ledger (``_res``), the staged-buffer
    table (``_device``) and the writeback queue (``_pending``) are
    guarded by ``_lock`` (see tools/slate_lint LOCK_REGISTRY) so a
    checkpoint/observer thread can read residency while the factorization
    thread streams.  Blocking work — chaos stalls, host materialization —
    happens OUTSIDE the lock.
    """

    def __init__(self, dense: np.ndarray, mb: int, nb: int,
                 max_pending: int = 4):
        slate_error(np.ndim(dense) == 2, "TileMap needs a 2D host array")
        self._host = np.array(dense, copy=True, order="C")
        self.m, self.n = self._host.shape
        self.mb, self.nb = int(mb), int(nb)
        # writeback queue depth before a forced drain: bounds how much
        # device memory in-flight D2H copies can pin
        self.max_pending = max(1, int(max_pending))
        self.Mt = layout.num_tiles(self.m, self.mb)
        self.Nt = layout.num_tiles(self.n, self.nb)
        self._res = np.zeros((self.Mt, self.Nt), np.uint8)
        self._device: dict[tuple, Any] = {}
        self._pending: list[tuple] = []
        self._lock = threading.Lock()

    @classmethod
    def from_dense(cls, dense, mb: int, nb: int) -> "TileMap":
        return cls(np.asarray(dense), mb, nb)

    # ---- residency ledger ----
    def _tiles_of(self, r0, r1, c0, c1):
        return (slice(r0 // self.mb, -(-r1 // self.mb)),
                slice(c0 // self.nb, -(-c1 // self.nb)))

    def residency(self, i: int, j: int) -> str:
        """Residency of tile (i, j): 'host' | 'device' | 'dirty'."""
        with self._lock:
            return _RES_NAMES[int(self._res[i, j])]

    def residency_counts(self) -> dict:
        with self._lock:
            counts = np.bincount(self._res.reshape(-1), minlength=3)
        return {name: int(counts[code]) for code, name in _RES_NAMES.items()}

    @staticmethod
    def _stall() -> None:
        # chaos: a congested host<->device copy path (docs/ROBUSTNESS.md);
        # the sleep must stay outside _lock (CON003)
        from ..robust import faults
        plan = faults.host_fire("ooc_copy_stall")
        if plan is not None and plan.delay_s > 0:
            time.sleep(plan.delay_s)

    @staticmethod
    def _hits(key: tuple, other: tuple) -> bool:
        return not (other[1] <= key[0] or other[0] >= key[1]
                    or other[3] <= key[2] or other[2] >= key[3])

    # ---- streaming ----
    def prefetch(self, r0: int, r1: int, c0: int, c1: int) -> None:
        """Stage host window [r0:r1, c0:c1] on device (async H2D)."""
        key = (int(r0), int(r1), int(c0), int(c1))
        with self._lock:
            staged = key in self._device
            conflict = any(self._hits(key, p[0]) for p in self._pending)
        if staged:
            return
        self._stall()
        if conflict:
            self.drain()
        buf = jax.device_put(self._host[r0:r1, c0:c1])
        ti, tj = self._tiles_of(*key)
        with self._lock:
            self._device[key] = buf
            self._res[ti, tj] = np.maximum(self._res[ti, tj], _RES_DEVICE)

    def fetch(self, r0: int, r1: int, c0: int, c1: int):
        """Consume the staged window (pop), or H2D it on a miss.

        A window overlapping a pending writeback drains first, so a
        fetch always observes the newest bytes; disjoint windows ride
        through without serializing against in-flight D2H copies."""
        key = (int(r0), int(r1), int(c0), int(c1))
        with self._lock:
            buf = self._device.pop(key, None)
            conflict = any(self._hits(key, p[0]) for p in self._pending)
        if buf is not None:
            return buf
        self._stall()
        if conflict:
            self.drain()
        buf = jax.device_put(self._host[r0:r1, c0:c1])
        ti, tj = self._tiles_of(*key)
        with self._lock:
            self._res[ti, tj] = np.maximum(self._res[ti, tj], _RES_DEVICE)
        return buf

    def store(self, r0: int, r1: int, c0: int, c1: int, arr) -> None:
        """Queue an async writeback of device ``arr`` into the window."""
        key = (int(r0), int(r1), int(c0), int(c1))
        slate_error(arr.shape == (r1 - r0, c1 - c0),
                    f"store shape {arr.shape} != window "
                    f"({r1 - r0},{c1 - c0})")
        self._stall()
        if hasattr(arr, "copy_to_host_async"):
            arr.copy_to_host_async()
        ti, tj = self._tiles_of(*key)
        with self._lock:
            self._pending.append((key, arr))
            depth = len(self._pending)
            self._res[ti, tj] = _RES_DIRTY
            # staged clean copies overlapping a dirty window are stale
            for k in [k for k in self._device if self._hits(key, k)]:
                del self._device[k]
        if depth > self.max_pending:
            self.drain()

    def drain(self) -> None:
        """Land every pending writeback in host RAM (blocks)."""
        with self._lock:
            pending, self._pending = self._pending, []
        for (r0, r1, c0, c1), arr in pending:
            self._host[r0:r1, c0:c1] = np.asarray(arr)
        if pending:
            with self._lock:
                for (r0, r1, c0, c1), _ in pending:
                    ti, tj = self._tiles_of(r0, r1, c0, c1)
                    self._res[ti, tj] = _RES_HOST

    def permute_rows(self, r0: int, c0: int, c1: int, perm) -> None:
        """Host-side row permutation of the window [r0:, c0:c1] — the LU
        left-columns pivot exchange: pure memory traffic, so it stays on
        the host where the authoritative bytes already live."""
        self.drain()
        if c1 > c0:
            self._host[r0:, c0:c1] = self._host[r0:, c0:c1][np.asarray(perm)]

    # ---- host views ----
    def host_array(self) -> np.ndarray:
        """The authoritative host bytes after draining writebacks.

        Returns the live backing array (no copy): callers snapshotting it
        (the checkpoint writer) must copy or serialize before the next
        factorization step mutates it."""
        self.drain()
        return self._host

    def to_dense(self) -> np.ndarray:
        return self.host_array().copy()

    @property
    def dtype(self):
        return self._host.dtype

    @property
    def nbytes(self) -> int:
        return self._host.nbytes

    def __repr__(self):
        counts = self.residency_counts()
        return (f"TileMap({self.m}x{self.n}, tiles {self.mb}x{self.nb}, "
                f"{self.dtype}, residency {counts})")
