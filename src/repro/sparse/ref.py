"""Pure-numpy oracles for the paper applications (§IV-A) plus k-core.

Independent implementations (no task engine, no tile grid) used to verify
the DCRA execution paths bit-for-bit / to float tolerance. Frontier work is
vectorized over CSR rows (no per-vertex Python loop), so the oracles keep
up with Graph500-scale graphs (RMAT-22, ~1.3e8 edges).
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

from .csr import CSR

INF = np.float64(np.inf)


def _edge_ids(g: CSR, verts: np.ndarray) -> np.ndarray:
    """Indices into ``g.col_idx`` of every out-edge of ``verts``."""
    starts = g.row_ptr[verts]
    counts = g.row_ptr[verts + 1] - starts
    first = np.cumsum(counts) - counts          # output offset of each row
    return (np.arange(int(counts.sum()), dtype=np.int64)
            + np.repeat(starts - first, counts))


def bfs_ref(g: CSR, root: int) -> np.ndarray:
    """Hop count from root; -1 if unreachable."""
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


def sssp_ref(g: CSR, root: int) -> np.ndarray:
    """Shortest path weights (frontier Bellman-Ford: only vertices whose
    distance dropped relax their out-edges); inf if unreachable."""
    dist = np.full(g.n, np.inf)
    dist[root] = 0.0
    frontier = np.array([root])
    while len(frontier):
        eids = _edge_ids(g, frontier)
        src = np.repeat(frontier, np.diff(g.row_ptr)[frontier])
        upd = np.full(g.n, np.inf)
        np.minimum.at(upd, g.col_idx[eids],
                      dist[src] + g.values[eids].astype(np.float64))
        frontier = np.flatnonzero(upd < dist)
        dist = np.minimum(dist, upd)
    return dist


def pagerank_ref(g: CSR, damping: float = 0.85, iters: int = 20) -> np.ndarray:
    deg = g.degrees().astype(np.float64)
    rank = np.full(g.n, 1.0 / g.n)
    rows = g.row_of()
    for _ in range(iters):
        contrib = np.where(deg > 0, rank / np.maximum(deg, 1), 0.0)
        acc = np.bincount(g.col_idx, weights=contrib[rows], minlength=g.n)
        # dangling mass redistributed uniformly
        dangling = rank[deg == 0].sum()
        rank = (1 - damping) / g.n + damping * (acc + dangling / g.n)
    return rank


def wcc_ref(g: CSR) -> np.ndarray:
    """Weakly connected components, each labelled by its smallest vertex
    id — the fixed point of min-label propagation over both edge
    directions (graph coloring per the paper [78])."""
    adj = coo_matrix((np.ones(g.nnz, np.int8), (g.row_of(), g.col_idx)),
                     shape=(g.n, g.n))
    n_comp, comp = connected_components(adj, directed=True,
                                        connection="weak")
    first = np.full(n_comp, g.n, np.int64)
    np.minimum.at(first, comp, np.arange(g.n, dtype=np.int64))
    return first[comp]


def spmv_ref(g: CSR, x: np.ndarray) -> np.ndarray:
    rows = g.row_of()
    return np.bincount(rows, weights=g.values * x[g.col_idx],
                       minlength=g.n).astype(np.float64)


def histogram_ref(elements: np.ndarray, n_bins: int) -> np.ndarray:
    return np.bincount(elements, minlength=n_bins).astype(np.int64)


def kcore_ref(g: CSR, k: int) -> np.ndarray:
    """k-core by iterative peel on the undirected view (degree counts each
    stored edge direction, like ``wcc_ref``'s both-ways propagation).

    Returns each surviving vertex's within-core degree, -1 if peeled.
    """
    gt = g.transpose()
    deg = (g.degrees() + gt.degrees()).astype(np.int64)
    alive = np.ones(g.n, bool)
    frontier = np.flatnonzero(deg < k)
    while len(frontier):
        # a peeled vertex decrements every out- and in-neighbour once
        dec = (np.bincount(g.col_idx[_edge_ids(g, frontier)],
                           minlength=g.n)
               + np.bincount(gt.col_idx[_edge_ids(gt, frontier)],
                             minlength=g.n))
        alive[frontier] = False
        deg = deg - dec
        frontier = np.flatnonzero(alive & (deg < k))
    return np.where(alive, deg, -1).astype(np.int64)
