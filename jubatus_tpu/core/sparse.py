"""Padded sparse-vector batches — the host↔device interchange format.

The reference keeps feature vectors as string-keyed sparse maps
(core::fv_converter sfv, consumed per-datum under a write lock — SURVEY.md
§3.2). On TPU the model plane wants fixed shapes: a feature vector is hashed
into a 2^k index space (fv/hashing.py) and a *batch* of vectors is a pair of
dense arrays (indices, values) padded to a common nnz. Padding entries carry
value 0.0 so they are no-ops in every kernel (gathers multiply by 0, scatter
adds add 0).

Pad widths follow the rows: the widest row is rounded up to a rung of an
eighth-octave ladder (``_width_bucket``: at most an eighth of padding, 8
rungs per doubling), so XLA compiles O(log max_nnz) programs, not one per
batch shape, and a 39-feature row runs at 40, not 64; rows of uneven
length keep to powers of two (``_request_width``) and are trained as
slabs (models/classifier.py _cut_slabs). Row counts are bucketed to
powers of two (``_bucket``).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

# (index, weight) pairs, already hashed. The canonical sparse vector type.
SparseVector = List[Tuple[int, float]]


def _bucket(n: int, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _width_bucket(n: int, minimum: int = 8) -> int:
    """The width a row of ``n`` entries is padded to: the smallest
    ``m * 2**e >= n`` with m in 8..16, never under 8 (a sublane group) nor
    under ``minimum``. Every multiple of 8 up to 128, then steps of 16 to
    256, 32 to 512, 64 to 1024...: at most an eighth of padding where a
    power of two gave up to half (gather and scatter cost per entry,
    padding included), 8 compiled widths per doubling. The same arithmetic
    as ``pack`` in native/fast_ingest.cpp (tests/test_sparse_width.py holds
    the two equal)."""
    n = max(n, minimum, 8)
    step = 8
    while step * 16 < n:
        step *= 2
    return (n + step - 1) // step * step


def _request_width(counts: np.ndarray, minimum: int = 8) -> int:
    """The width the rows of one request are packed at, ``counts`` their
    entries: the ladder's rung of the fullest row where the rows fill at
    least half of their entries at that rung (rows that are alike: 39 of
    40, 780 of 832), else the power of two at or above the fullest row.
    Where lengths are heavy-tailed the fullest row's rung says nothing of
    the padding, which rung a request lands on is chance, and every rung
    is a compiled program; on powers of two a server's life sees one
    program a doubling. What it decides: the width a request travels at
    from the parser to the driver (a flush is padded to its widest
    request's), and the width a classify and the quality plane's scoring
    of a call's first rows run at, padding included (nine entries in ten
    of a 500-document call). It no longer decides the train program: a
    train flush of such rows is handed to the chip as slabs
    (models/classifier.py _cut_slabs). It goes when requests come off the
    parser, and scores run, in a form without the padding. The same
    arithmetic as ``pack`` in native/fast_ingest.cpp
    (tests/test_sparse_width.py holds the two equal)."""
    most = int(counts.max()) if counts.size else 1
    rung = _width_bucket(most, minimum)
    if 2 * int(counts.sum()) >= counts.size * rung:
        return rung
    return 1 << (max(most, minimum, 8) - 1).bit_length()


class CSRBatch:
    """Arena-style batch of hashed sparse feature vectors (CSR triple).

    The batch converter (core/fv/converter.py convert_batch) emits one of
    these instead of B per-datum SparseVector lists: three flat arrays,
    no per-entry Python objects, ready for a single vectorized pad into
    the device interchange format (``to_padded`` → SparseBatch).

    Attributes:
      indices:     int32   [nnz]  hashed feature indices, per-row sorted
      values:      float32 [nnz]  feature values
      row_offsets: int64   [B+1]  row i spans [row_offsets[i], row_offsets[i+1])
    """

    __slots__ = ("indices", "values", "row_offsets")

    def __init__(self, indices: np.ndarray, values: np.ndarray,
                 row_offsets: np.ndarray) -> None:
        assert indices.shape == values.shape and indices.ndim == 1
        assert row_offsets.ndim == 1 and row_offsets[-1] == indices.shape[0]
        self.indices = indices
        self.values = values
        self.row_offsets = row_offsets

    @property
    def batch_size(self) -> int:
        return self.row_offsets.shape[0] - 1

    @property
    def nnz(self) -> int:
        return self.indices.shape[0]

    def __len__(self) -> int:
        return self.batch_size

    def row(self, i: int) -> SparseVector:
        """One row as the canonical (index, value) pair list — for the
        instance engines that store per-row vectors (NN backends)."""
        lo, hi = int(self.row_offsets[i]), int(self.row_offsets[i + 1])
        return list(zip(self.indices[lo:hi].tolist(),
                        self.values[lo:hi].astype(np.float64).tolist()))

    def rows(self) -> List[SparseVector]:
        return [self.row(i) for i in range(self.batch_size)]

    @classmethod
    def from_vectors(cls, vectors: Sequence[SparseVector]) -> "CSRBatch":
        """Pack per-datum SparseVectors (the per-datum converter's output)
        — the parity bridge between the two pipelines."""
        counts = np.fromiter((len(v) for v in vectors), dtype=np.int64,
                             count=len(vectors))
        off = np.zeros(len(vectors) + 1, dtype=np.int64)
        np.cumsum(counts, out=off[1:])
        idx = np.zeros(int(off[-1]), dtype=np.int32)
        val = np.zeros(int(off[-1]), dtype=np.float32)
        for i, vec in enumerate(vectors):
            if not vec:
                continue
            lo = int(off[i])
            idx[lo:lo + len(vec)] = [j for j, _ in vec]
            val[lo:lo + len(vec)] = [w for _, w in vec]
        return cls(idx, val, off)

    def uniform_row(self) -> Optional[np.ndarray]:
        """The shared index row if EVERY row carries the same index vector
        (fixed key schema — the common production feed), else None.
        The dense submatrix train plan's condition (ops.train_batch_schema)."""
        b = self.batch_size
        if b == 0:
            return None
        counts = np.diff(self.row_offsets)
        k = int(counts[0])
        if k == 0 or not (counts == k).all():
            return None
        mat = self.indices.reshape(b, k)
        if b > 1 and not (mat == mat[0]).all():
            return None
        return mat[0]

    def to_padded(self, min_width: int = 8,
                  batch_bucket: int = 1) -> "SparseBatch":
        """Vectorized pad into the [B, K] device interchange format —
        the CSR equivalent of SparseBatch.from_vectors (same width rule
        and optional row bucketing, no Python per-row loop)."""
        b = self.batch_size
        counts = np.diff(self.row_offsets)
        bsz = _bucket(max(b, 1), batch_bucket) if batch_bucket > 1 \
            else max(b, 1)
        width = _request_width(counts, min_width)
        idx = np.zeros((bsz, width), dtype=np.int32)
        val = np.zeros((bsz, width), dtype=np.float32)
        if self.nnz:
            rows = np.repeat(np.arange(b), counts)
            cols = np.arange(self.nnz) - np.repeat(
                self.row_offsets[:-1], counts)
            idx[rows, cols] = self.indices
            val[rows, cols] = self.values
        return SparseBatch(idx, val)


class SparseBatch:
    """A batch of hashed sparse feature vectors as padded numpy arrays.

    Attributes:
      idx:  int32  [B, K] feature indices (0 for padding)
      val:  float32 [B, K] feature values (0.0 for padding)
    """

    __slots__ = ("idx", "val")

    def __init__(self, idx: np.ndarray, val: np.ndarray) -> None:
        assert idx.shape == val.shape and idx.ndim == 2
        self.idx = idx
        self.val = val

    @property
    def batch_size(self) -> int:
        return self.idx.shape[0]

    @property
    def width(self) -> int:
        return self.idx.shape[1]

    @classmethod
    def from_vectors(
        cls,
        vectors: Sequence[SparseVector],
        min_width: int = 8,
        batch_bucket: int = 1,
    ) -> "SparseBatch":
        """Pack hashed sparse vectors into padded arrays.

        Widths follow ``_request_width`` (a rung of ``_width_bucket``'s
        ladder, or a power of two for uneven rows; optionally batch sizes
        a power of two) to bound the number of distinct XLA compilations.
        """
        n = len(vectors)
        bsz = _bucket(max(n, 1), batch_bucket) if batch_bucket > 1 else max(n, 1)
        width = _request_width(
            np.fromiter((len(v) for v in vectors), np.int64, n), min_width)
        idx = np.zeros((bsz, width), dtype=np.int32)
        val = np.zeros((bsz, width), dtype=np.float32)
        for i, vec in enumerate(vectors):
            if not vec:
                continue
            k = len(vec)
            idx[i, :k] = [j for j, _ in vec]
            val[i, :k] = [w for _, w in vec]
        return cls(idx, val)

    def pad_aux(self, aux: Sequence, fill=0, dtype=None) -> np.ndarray:
        """Pad a per-example array (labels, targets) to this batch's row count.

        Required when batch_bucket > 1 added all-zero padding rows: training
        kernels gate updates on ||x||^2 > 0, so padded rows are no-ops for
        any in-range fill value.
        """
        out = np.full(self.batch_size, fill, dtype=dtype or np.asarray(aux).dtype)
        out[: len(aux)] = aux
        return out

    def squared_norms(self) -> np.ndarray:
        return (self.val.astype(np.float64) ** 2).sum(axis=1)

    def __len__(self) -> int:
        return self.batch_size
