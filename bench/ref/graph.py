"""Plain numpy oracles for the graph cells, kept with the benchmark.

Copies of ``repro.sparse.ref.bfs_ref`` and ``pagerank_ref`` (checked
against them in ``bench/test_bench_ref.py``), plus the control of the
PageRank cell: the same iteration with its state held in bfloat16, the
precision below the float32 the configuration states. Nothing here imports
the program.
"""
from __future__ import annotations

import numpy as np

from .rmat import Graph


def _edge_ids(g: Graph, verts: np.ndarray) -> np.ndarray:
    """Indices into ``g.col_idx`` of every out-edge of ``verts``."""
    starts = g.row_ptr[verts]
    counts = g.row_ptr[verts + 1] - starts
    first = np.cumsum(counts) - counts
    return (np.arange(int(counts.sum()), dtype=np.int64)
            + np.repeat(starts - first, counts))


def bfs(g: Graph, root: int) -> np.ndarray:
    """Hop count from ``root``; -1 where unreachable."""
    dist = np.full(g.n, -1, np.int64)
    dist[root] = 0
    frontier = np.array([root])
    level = 0
    while len(frontier):
        level += 1
        nbrs = g.col_idx[_edge_ids(g, frontier)]
        new = np.unique(nbrs[dist[nbrs] < 0])
        dist[new] = level
        frontier = new
    return dist


def _bf16(a: np.ndarray) -> np.ndarray:
    """Round float values to the nearest bfloat16 (ties to even)."""
    bits = np.asarray(a, np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.view(np.float32).astype(np.float64)


def pagerank(g: Graph, damping: float, iters: int,
             bf16: bool = False) -> np.ndarray:
    """Power iteration with the dangling mass spread uniformly, in float64.

    ``bf16=True`` is the control: rank, contributions and sums rounded to
    bfloat16 after each operation.
    """
    q = _bf16 if bf16 else (lambda a: a)
    deg = g.degrees().astype(np.float64)
    rank = q(np.full(g.n, 1.0 / g.n))
    rows = g.rows()
    for _ in range(iters):
        contrib = q(np.where(deg > 0, rank / np.maximum(deg, 1), 0.0))
        acc = q(np.bincount(g.col_idx, weights=contrib[rows], minlength=g.n))
        dangling = rank[deg == 0].sum()
        rank = q((1 - damping) / g.n + damping * (acc + dangling / g.n))
    return rank


def max_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest per-vertex |got - want| / |want| (PageRank ranks are > 0)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))
