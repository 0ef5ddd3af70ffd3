"""CSR sparse container (the paper's dataset format, §IV-A)."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class CSR:
    """Compressed Sparse Row: three arrays, no partitioning (paper §IV-A).

    A launched graph is resident: the first graph-program launch on a CSR
    packs its edges and keeps them, on the host and on the fabric, for
    every later launch on the same object
    (:func:`repro.sparse.program.packed_graph`). Packing marks the three
    arrays read-only, so an in-place edit raises; a changed graph is a new
    ``CSR`` (or a field assigned a new array), which packs again.
    """
    row_ptr: np.ndarray   # [V+1] int64
    col_idx: np.ndarray   # [E] int32
    values: np.ndarray    # [E] float32 (edge weights / nonzeros)

    @property
    def n(self) -> int:
        return len(self.row_ptr) - 1

    @property
    def nnz(self) -> int:
        return len(self.col_idx)

    def degrees(self) -> np.ndarray:
        return np.diff(self.row_ptr)

    def row_of(self) -> np.ndarray:
        """Row index of every nonzero (repeat rows by degree)."""
        return np.repeat(np.arange(self.n, dtype=np.int64), self.degrees())

    def transpose(self) -> "CSR":
        order = np.argsort(self.col_idx, kind="stable")
        rows_t = self.col_idx[order]
        cols_t = self.row_of()[order].astype(np.int32)
        vals_t = self.values[order]
        rp = np.zeros(self.n + 1, np.int64)
        np.add.at(rp, rows_t + 1, 1)
        return CSR(np.cumsum(rp), cols_t, vals_t)

    def memory_bytes(self) -> int:
        return (self.row_ptr.nbytes + self.col_idx.nbytes + self.values.nbytes)


def from_edges(n: int, src: np.ndarray, dst: np.ndarray,
               values: np.ndarray | None = None) -> CSR:
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    if values is None:
        values = np.ones(len(src), np.float32)
    else:
        values = values[order]
    rp = np.zeros(n + 1, np.int64)
    np.add.at(rp, src + 1, 1)
    return CSR(np.cumsum(rp), dst.astype(np.int32), values.astype(np.float32))
