"""The Graph500 Kronecker (RMAT) generator, kept with the benchmark.

A copy of the generator the program ships (``repro.sparse.datasets.rmat``
with ``from_edges``), so that no later change to the program can change the
graphs the benchmark measures on. It draws the same random numbers in the
same order and gives the same graph, which ``bench/test_bench_ref.py``
checks; it deduplicates by sorting edge keys, which leaves them in CSR
order, where the original sorts twice. Nothing here imports the program.

Graph500 generator: 2**scale vertices, edge_factor * 2**scale edge draws,
each a walk down ``scale`` quadrant choices with probabilities A, B, C and
1 - A - B - C; vertex ids permuted, self-loops dropped, both directions
stored, duplicates removed. Edge weights are integers 1..255 stored as f32
(an assumption: Graph500 SSSP draws uniform floats). :func:`graph` then cuts
the graph to the configuration's fixed edge count (another assumption), so
that the program, which compiles for its edge count, compiles once for
every seed.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Graph(NamedTuple):
    """A CSR graph as three numpy arrays (row pointers, targets, weights)."""
    row_ptr: np.ndarray    # [V + 1] int64
    col_idx: np.ndarray    # [E] int32
    values: np.ndarray     # [E] float32

    @property
    def n(self) -> int:
        return len(self.row_ptr) - 1

    @property
    def nnz(self) -> int:
        return len(self.col_idx)

    def degrees(self) -> np.ndarray:
        return np.diff(self.row_ptr)

    def rows(self) -> np.ndarray:
        """Source vertex of every stored edge."""
        return np.repeat(np.arange(self.n, dtype=np.int64), self.degrees())


def _pairs(scale: int, n_edges: int, rng, a: float, b: float, c: float):
    src = np.zeros(n_edges, np.int64)
    dst = np.zeros(n_edges, np.int64)
    for _ in range(scale):
        u = rng.random(n_edges)
        row = u >= a + b                                   # lower half
        col = ((u >= a) & (u < a + b)) | (u >= a + b + c)  # right half
        src = (src << 1) | row
        dst = (dst << 1) | col
    return src, dst


def rmat(scale: int, edge_factor: int, seed: int, a: float = 0.57,
         b: float = 0.19, c: float = 0.19) -> Graph:
    """The undirected, deduplicated Graph500 graph of ``scale`` from ``seed``."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    src, dst = _pairs(scale, n * edge_factor, rng, a, b, c)
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    keep = src != dst
    src, dst = src[keep], dst[keep]
    # both directions, deduplicated; the sorted unique keys leave the
    # edges in row order, so they are the CSR's edge arrays as they stand
    keys = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    src, dst = keys // n, keys % n
    w = rng.integers(1, 256, len(keys)).astype(np.float32)
    row_ptr = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=n))])
    return Graph(row_ptr.astype(np.int64), dst.astype(np.int32), w)


def cut_to(g: Graph, n_edges: int, seed: int) -> Graph:
    """``g`` with exactly ``n_edges`` directed edges: undirected edges drawn
    from ``seed`` are dropped in both directions. Every seed's graph then
    has the same shapes."""
    drop_n, odd = divmod(g.nnz - n_edges, 2)
    if odd or drop_n < 0:
        raise ValueError(f"cannot cut {g.nnz} directed edges to {n_edges}")
    rows = g.rows()
    pairs = np.flatnonzero(rows < g.col_idx)        # each edge once
    rng = np.random.default_rng(np.random.SeedSequence((seed, 1)))
    drop = pairs[rng.choice(len(pairs), drop_n, replace=False)]
    u, v = rows[drop], g.col_idx[drop].astype(np.int64)
    keys = rows * g.n + g.col_idx                   # ascending: CSR order
    keep = np.ones(g.nnz, bool)
    keep[np.searchsorted(keys, np.concatenate([u * g.n + v, v * g.n + u]))] = False
    row_ptr = np.concatenate([[0], np.cumsum(np.bincount(rows[keep],
                                                         minlength=g.n))])
    return Graph(row_ptr.astype(np.int64), g.col_idx[keep], g.values[keep])


def graph(cfg: dict, seed: int) -> Graph:
    """The graph of configuration ``cfg`` for ``seed``."""
    g = rmat(int(cfg["scale"]), int(cfg["edge_factor"]), seed,
             a=cfg["A"], b=cfg["B"], c=cfg["C"])
    return cut_to(g, int(cfg["edges"]), seed)
