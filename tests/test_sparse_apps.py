"""The six paper apps vs pure-numpy oracles — exact results + stats sanity."""
import numpy as np
import pytest

from repro.core import EngineConfig, TaskEngine, TileGrid
from repro.sparse import apps, datasets, ref


@pytest.fixture(scope="module")
def graph():
    return datasets.rmat(10, edge_factor=8, seed=2)


@pytest.fixture()
def engine(graph):
    grid = TileGrid(8, 8, "hier_torus", die_rows=4, die_cols=4)
    return TaskEngine(EngineConfig(grid=grid), graph.n)


def test_bfs(graph, engine):
    d, stats = apps.bfs(engine, graph, 0)
    assert np.array_equal(d, ref.bfs_ref(graph, 0))
    assert stats.total_messages > 0 and stats.total_hops > 0


def test_sssp(graph, engine):
    d, _ = apps.sssp(engine, graph, 0)
    assert np.allclose(d, ref.sssp_ref(graph, 0))


def test_pagerank(graph, engine):
    d, stats = apps.pagerank(engine, graph, iters=5)
    assert np.allclose(d, ref.pagerank_ref(graph, iters=5), atol=1e-12)
    assert any(r.barrier for r in stats.rounds)   # epochs marked


def test_wcc(graph, engine):
    d, _ = apps.wcc(engine, graph)
    assert np.array_equal(d, ref.wcc_ref(graph))


def test_spmv(graph, engine):
    x = np.random.default_rng(0).random(graph.n)
    y, _ = apps.spmv(engine, graph, x)
    assert np.allclose(y, ref.spmv_ref(graph, x))


def test_histogram(engine):
    els = datasets.histogram_data(1 << 12, 64)
    h, _ = apps.histogram(engine, els, 64)
    assert np.array_equal(h, ref.histogram_ref(els, 64))


def test_wiki_like_shape():
    g = datasets.wiki_like(512, avg_degree=8)
    assert g.n == 512 and g.nnz > 512
    # heavier-tailed in-degree than out-degree
    indeg = g.transpose().degrees()
    assert indeg.max() > np.median(indeg) * 4


# ---- the vectorized oracles vs plain per-vertex loop references ----------

def _loop_bfs(g, root):
    from collections import deque
    dist = [-1] * g.n
    dist[root] = 0
    todo = deque([root])
    while todo:
        u = todo.popleft()
        for v in g.col_idx[g.row_ptr[u]:g.row_ptr[u + 1]]:
            if dist[v] < 0:
                dist[v] = dist[u] + 1
                todo.append(v)
    return np.array(dist, np.int64)


def _loop_sssp(g, root):
    import heapq
    dist = [np.inf] * g.n
    dist[root] = 0.0
    heap = [(0.0, root)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for e in range(g.row_ptr[u], g.row_ptr[u + 1]):
            v, nd = g.col_idx[e], d + float(g.values[e])
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(heap, (nd, v))
    return np.array(dist)


def _loop_wcc(g):
    parent = list(range(g.n))

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a
    for u in range(g.n):
        for v in g.col_idx[g.row_ptr[u]:g.row_ptr[u + 1]]:
            a, b = find(u), find(int(v))
            parent[max(a, b)] = min(a, b)    # the root is the smallest id
    return np.array([find(u) for u in range(g.n)], np.int64)


def _loop_kcore(g, k):
    nbrs = [[] for _ in range(g.n)]
    for u in range(g.n):
        for v in g.col_idx[g.row_ptr[u]:g.row_ptr[u + 1]]:
            nbrs[u].append(int(v))
            nbrs[int(v)].append(u)
    deg = [len(a) for a in nbrs]
    alive = [True] * g.n
    while True:
        peel = [u for u in range(g.n) if alive[u] and deg[u] < k]
        if not peel:
            break
        for u in peel:
            alive[u] = False
        for u in peel:
            for v in nbrs[u]:
                deg[v] -= 1
    return np.array([d if a else -1 for d, a in zip(deg, alive)], np.int64)


@pytest.mark.parametrize("g", [
    datasets.rmat(9, edge_factor=8, seed=4),
    datasets.rmat(8, edge_factor=4, seed=5, undirected=False),
    datasets.disconnected_pair(64),
], ids=["rmat9", "rmat8-directed", "disconnected"])
def test_oracles_match_loop_references(g):
    roots = (0, int(np.argmax(g.degrees())))
    for r in roots:
        assert np.array_equal(ref.bfs_ref(g, r), _loop_bfs(g, r))
        assert np.array_equal(ref.sssp_ref(g, r), _loop_sssp(g, r))
    assert np.array_equal(ref.wcc_ref(g), _loop_wcc(g))
    for k in (2, 6):
        assert np.array_equal(ref.kcore_ref(g, k), _loop_kcore(g, k))
