"""Shared protocol for columnar MTable column classes.

A columnar column stores n logical cells as dense arrays and duck-types
the 1-D object-ndarray surface MTable uses (``shape``/``dtype``/
``len``/int-vs-fancy indexing/iteration/``copy``), materializing a
per-row Python value only when a consumer actually asks for one.
Subclasses implement ``_render_row`` (one cell), ``_subset`` (row
selection -> same column type), ``__len__``, ``copy`` and optionally
``concat_same`` (same-typed concatenation for MTable.concat_rows).
"""

from __future__ import annotations

import functools

import numpy as np


class ColumnarColumn:
    __mtable_column__ = True
    dtype = np.dtype(object)

    def _render_row(self, i: int):  # pragma: no cover - interface
        raise NotImplementedError

    def _subset(self, sel):  # pragma: no cover - interface
        raise NotImplementedError

    def __len__(self):  # pragma: no cover - interface
        raise NotImplementedError

    @property
    def shape(self):
        return (len(self),)

    def __getitem__(self, i):
        if isinstance(i, (int, np.integer)):
            return self._render_row(int(i))
        return self._subset(i)

    def __iter__(self):
        for i in range(len(self)):
            yield self._render_row(i)

    def concat_same(self, other):
        return None

    def materialize(self) -> np.ndarray:
        out = np.empty(len(self), object)
        out[:] = list(self)
        return out


#: lanes of a device vector register: the minor axis of a dense block
LANES = 128
#: rows of a block are a multiple of this (8 sublanes x 128 lanes), so a
#: block's (S, 128) slabs tile the device's registers with no padding
BLOCK_QUANTUM = 8 * LANES
#: the same for a one-byte table (the trees' uint8 bins): a register tile
#: of bytes is 32 sublanes deep, so S must be a multiple of 32
BYTE_BLOCK_QUANTUM = 32 * LANES
DEFAULT_BLOCK_ROWS = 1 << 16


def sublane_quantum(dtype) -> int:
    """Sublanes a register tile of ``dtype`` is deep: 8 for four-byte
    values, 16 for two, 32 for one. A block's ``S`` is a multiple of it,
    so a block of bytes is whole 8-bit tiles."""
    return 8 * max(1, 4 // np.dtype(dtype).itemsize)


class DenseBlockColumn(ColumnarColumn):
    """A VECTOR column of ``n_rows`` dense rows of one width, held as ONE
    feature-major, lane-packed array ``blocks`` of shape ``(row_blocks,
    dim, S, 128)``: row ``r`` of block ``b`` is ``blocks[b, :, r // 128,
    r % 128]``, and rows past ``n_rows`` in the last block are zero. The
    array may be a host ``numpy`` array or a device-resident ``jax.Array``
    (a cached table, as a Flink user caches a ``DataSet``); trainers take
    it as it is — ``extract_design`` hands the column through, the BSP
    engine partitions it on its leading axis — so a table of deployment
    size is never copied on the host or doubled on the device. Per-row
    access (``col[i]``, iteration, ``rows()``) fetches and is for small
    tables and tests."""

    __slots__ = ("blocks", "n_rows")

    def __init__(self, blocks, n_rows: int):
        shape = tuple(blocks.shape)
        sub = sublane_quantum(blocks.dtype)
        if len(shape) != 4 or shape[3] != LANES or shape[2] % sub:
            raise ValueError(
                f"DenseBlockColumn: blocks of {np.dtype(blocks.dtype)} must "
                f"be (row_blocks, dim, S, {LANES}) with S a multiple of "
                f"{sub}, got {shape}")
        if not 0 <= int(n_rows) <= shape[0] * shape[2] * LANES:
            raise ValueError(f"DenseBlockColumn: {n_rows} rows do not fit "
                             f"blocks of shape {shape}")
        self.blocks = blocks
        self.n_rows = int(n_rows)

    # -- geometry ---------------------------------------------------------
    @property
    def value_dtype(self) -> np.dtype:
        """What a cell of the table is stored as: float32 (float64 on the
        test mesh), or one byte a value (``uint8``: pixels, bins)."""
        return np.dtype(self.blocks.dtype)

    @property
    def dim(self) -> int:
        return int(self.blocks.shape[1])

    @property
    def block_rows(self) -> int:
        return int(self.blocks.shape[2]) * LANES

    @property
    def row_blocks(self) -> int:
        return int(self.blocks.shape[0])

    @property
    def on_device(self) -> bool:
        return not isinstance(self.blocks, np.ndarray)

    @staticmethod
    def block_rows_for(n_rows: int, block_rows: int = 0,
                       quantum: int = BLOCK_QUANTUM) -> int:
        """Rows a block holds: ``block_rows`` (default 65,536) rounded up
        to the quantum, and no more than ``n_rows`` needs."""
        want = int(block_rows) or DEFAULT_BLOCK_ROWS
        need = -(-max(int(n_rows), 1) // quantum) * quantum
        return min(-(-want // quantum) * quantum, need)

    @staticmethod
    def pack(X: np.ndarray, block_rows: int, row_blocks: int = 0
             ) -> np.ndarray:
        """Host rows ``(n, dim)`` (or per-row values ``(n,)``) laid out as
        blocks ``(row_blocks, dim, S, 128)`` (``(row_blocks, S, 128)``),
        zero past ``n``."""
        X = np.asarray(X)
        n = X.shape[0]
        nb = max(int(row_blocks), -(-n // block_rows), 1)
        flat = np.zeros((nb * block_rows,) + X.shape[1:], X.dtype)
        flat[:n] = X
        S = block_rows // LANES
        if X.ndim == 1:
            return flat.reshape(nb, S, LANES)
        return np.ascontiguousarray(
            flat.reshape(nb, S, LANES, X.shape[1]).transpose(0, 3, 1, 2))

    @classmethod
    def from_rows(cls, X: np.ndarray, block_rows: int = 0
                  ) -> "DenseBlockColumn":
        X = np.asarray(X)
        if X.ndim != 2:
            raise ValueError("DenseBlockColumn.from_rows: X must be (n, dim)")
        B = cls.block_rows_for(X.shape[0], block_rows,
                               sublane_quantum(X.dtype) * LANES)
        return cls(cls.pack(X, B), X.shape[0])

    def to_rows(self) -> np.ndarray:
        """Host rows ``(n_rows, dim)`` (fetches a device-resident block)."""
        a = np.asarray(self.blocks)
        nb, d, S, _ = a.shape
        return a.transpose(0, 2, 3, 1).reshape(nb * S * LANES, d)[:self.n_rows]

    # -- the MTable column surface ---------------------------------------
    def __len__(self):
        return self.n_rows

    def _render_row(self, i: int):
        from .vector import DenseVector
        if not -self.n_rows <= i < self.n_rows:
            raise IndexError(i)
        b, r = divmod(i % self.n_rows, self.block_rows)
        return DenseVector(np.asarray(
            self.blocks[b, :, r // LANES, r % LANES], np.float64))

    def _subset(self, sel):
        return DenseBlockColumn.from_rows(self.to_rows()[sel],
                                          self.block_rows)

    def copy(self) -> "DenseBlockColumn":
        return DenseBlockColumn(self.blocks.copy(), self.n_rows)

    def materialize(self) -> np.ndarray:
        from .vector import DenseVector
        out = np.empty(self.n_rows, object)
        out[:] = [DenseVector(r.astype(np.float64)) for r in self.to_rows()]
        return out


class RowBlockColumn(ColumnarColumn):
    """A numeric column of ``n_rows`` values (labels, weights) laid out as
    the rows of a :class:`DenseBlockColumn` are: ``blocks`` is
    ``(row_blocks, S, 128)``, row ``r`` of block ``b`` at ``blocks[b, r //
    128, r % 128]``, zero past ``n_rows``. Host ``numpy`` or
    device-resident; a trainer that walks the table block by block reads
    the label of a row where it reads the row. Per-row access fetches and
    is for small tables and tests."""

    __slots__ = ("blocks", "n_rows")

    def __init__(self, blocks, n_rows: int):
        shape = tuple(blocks.shape)
        if len(shape) != 3 or shape[2] != LANES or shape[1] % 8:
            raise ValueError(
                f"RowBlockColumn: blocks must be (row_blocks, S, {LANES}) "
                f"with S a multiple of 8, got {shape}")
        if not 0 <= int(n_rows) <= shape[0] * shape[1] * LANES:
            raise ValueError(f"RowBlockColumn: {n_rows} rows do not fit "
                             f"blocks of shape {shape}")
        self.blocks = blocks
        self.n_rows = int(n_rows)

    @property
    def dtype(self):
        return np.dtype(self.blocks.dtype)

    @property
    def block_rows(self) -> int:
        return int(self.blocks.shape[1]) * LANES

    @property
    def on_device(self) -> bool:
        return not isinstance(self.blocks, np.ndarray)

    @classmethod
    def from_values(cls, v: np.ndarray, block_rows: int = 0
                    ) -> "RowBlockColumn":
        v = np.asarray(v)
        if v.ndim != 1:
            raise ValueError("RowBlockColumn.from_values: v must be (n,)")
        B = DenseBlockColumn.block_rows_for(v.shape[0], block_rows)
        return cls(DenseBlockColumn.pack(v, B), v.shape[0])

    def to_values(self) -> np.ndarray:
        """Host values ``(n_rows,)`` (fetches a device-resident column)."""
        return np.asarray(self.blocks).reshape(-1)[:self.n_rows]

    def __len__(self):
        return self.n_rows

    def _render_row(self, i: int):
        if not -self.n_rows <= i < self.n_rows:
            raise IndexError(i)
        b, r = divmod(i % self.n_rows, self.block_rows)
        return np.asarray(self.blocks[b, r // LANES, r % LANES]).item()

    def _subset(self, sel):
        return RowBlockColumn.from_values(self.to_values()[sel],
                                          self.block_rows)

    def copy(self) -> "RowBlockColumn":
        return RowBlockColumn(self.blocks.copy(), self.n_rows)

    def materialize(self) -> np.ndarray:
        return self.to_values()


def as_block_column(X, num_workers: int = 1,
                    quantum: int = BLOCK_QUANTUM) -> DenseBlockColumn:
    """The blocked trainers' one input form. A ``DenseBlockColumn`` passes
    through untouched (device-resident or not); host rows ``(n, d)`` are
    packed once into blocks of a multiple of ``quantum`` rows, their count
    a multiple of the workers so the engine has nothing to pad."""
    if isinstance(X, DenseBlockColumn):
        return X
    X = np.asarray(X)
    if X.ndim != 2:
        raise ValueError("X must be (n, d) rows or a DenseBlockColumn")
    quantum = max(quantum, sublane_quantum(X.dtype) * LANES)
    B = DenseBlockColumn.block_rows_for(X.shape[0], quantum=quantum)
    nb = -(-max(X.shape[0], 1) // B)
    nb = -(-nb // num_workers) * num_workers
    return DenseBlockColumn(DenseBlockColumn.pack(X, B, nb), X.shape[0])


def as_row_blocks(values, dtype, num_workers: int = 1, like=None):
    """Per-row values as blocks ``(row_blocks, S, 128)`` of ``dtype``, the
    form a trainer that walks plain columns (ids, ratings) takes: a
    :class:`RowBlockColumn` is used where it lies (device-resident or
    not), host values ``(n,)`` are packed once, their block count a
    multiple of the workers. ``like``: blocks the result must be laid out
    as (the table's other columns)."""
    if isinstance(values, RowBlockColumn):
        blocks = values.blocks
        if blocks.dtype != np.dtype(dtype):
            blocks = blocks.astype(dtype)
    else:
        v = np.asarray(values)
        if v.ndim != 1:
            raise ValueError("per-row values must be (n,)")
        if like is not None:
            B, nb = like.shape[1] * LANES, like.shape[0]
        else:
            B = DenseBlockColumn.block_rows_for(v.shape[0])
            nb = -(-max(v.shape[0], 1) // B)
            nb = -(-nb // num_workers) * num_workers
        blocks = DenseBlockColumn.pack(v.astype(dtype), B, nb)
    if like is not None and tuple(blocks.shape) != tuple(like.shape):
        raise ValueError(f"columns of one table must be laid out alike: "
                         f"{tuple(blocks.shape)} beside {tuple(like.shape)}")
    return blocks


def block_values(col: DenseBlockColumn, values):
    """Per-row ``values`` laid out like the table's rows, ``(row_blocks,
    S, 128)``: a :class:`RowBlockColumn` or an array already so laid out
    passes through where it lies, host values ``(n,)`` are packed,
    ``None`` gives ``None``."""
    nb, _, S, _ = col.blocks.shape
    if values is None:
        return None
    if isinstance(values, RowBlockColumn):
        if values.n_rows != col.n_rows:
            raise ValueError(f"{values.n_rows} values for {col.n_rows} rows")
        values = values.blocks
    if getattr(values, "shape", None) == (nb, S, LANES):
        return values
    v = np.asarray(values)
    if v.shape != (col.n_rows,):
        raise ValueError(f"per-row values must be ({col.n_rows},), "
                         f"got {v.shape}")
    return DenseBlockColumn.pack(v, col.block_rows, nb)


@functools.lru_cache(maxsize=None)
def _unit_weights_fn(dtype: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def unit_weights_like(blocks, n_rows):
        nb, _, S, L = blocks.shape
        at = jax.lax.broadcasted_iota(jnp.int32, (nb, S, L), 0) * (S * L) \
            + jax.lax.broadcasted_iota(jnp.int32, (nb, S, L), 1) * L \
            + jax.lax.broadcasted_iota(jnp.int32, (nb, S, L), 2)
        return (at < n_rows).astype(dtype)
    return unit_weights_like


def block_weights(col: DenseBlockColumn, sample_weight=None, dtype=None):
    """Per-row weights laid out like the table's rows, ``(row_blocks, S,
    128)``, zero on the padding past ``n_rows`` — the mask every pass of a
    blocked trainer carries. Without ``sample_weight`` they are made where
    the table lives (no ``(n,)`` host array); weights already so laid out
    pass through. ``dtype``: what the weights are held as where that is
    not the table's own (a table of bytes is weighted in floats)."""
    nb, _, S, _ = col.blocks.shape
    dt = np.dtype(dtype or col.blocks.dtype)
    if getattr(sample_weight, "shape", None) == (nb, S, LANES):
        return sample_weight              # already laid out (one fit, twice)
    if sample_weight is None:
        if col.on_device:
            return _unit_weights_fn(dt.name)(col.blocks, col.n_rows)
        w = np.zeros(nb * S * LANES, dt)
        w[:col.n_rows] = 1
        return w.reshape(nb, S, LANES)
    w = np.asarray(sample_weight, dt)
    if w.shape != (col.n_rows,):
        raise ValueError("sample_weight must be (n,)")
    return DenseBlockColumn.pack(w, col.block_rows, nb)
