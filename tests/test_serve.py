"""The resident serving runtime (:mod:`repro.serve`).

Part A — host-side pieces: tenant-graph expansion, column split, batch
padding, QueueConfig round budgets.

Part B (subprocess, 8 fake host devices) — the serving contract:

* a mixed stream of 4 tenants x 2 programs completes with every
  per-tenant result **bit-identical** to the equivalent standalone
  ``run_program`` launch;
* pre-warm populates exactly one compile-cache key per (program, graph,
  batch-width) shape class, and the whole request stream afterwards is
  cache hits only — zero new jit traces under serving load (the
  ``cache_stats``/``_cached`` serving-load coverage);
* admission control: an undersized per-tenant budget rejects with a
  retriable status (never a silent drop), accounting balances, and a
  drained tenant's retry is admitted; a request whose demand alone
  exceeds its budget is rejected NON-retriable (no futile retry loop);
* undersized *launch* queues produce NoC drops that are attributed to
  responses and stats, never swallowed;
* the MoE lane serves batched token blocks through one warm jitted
  dispatch (no re-trace after warm-up) and matches the einsum oracle;
* **continuous serving**: for every ``inflight_depth`` in {1, 2, 4} (and
  the DRR former, and donated buffers) the responses, per-tenant ledger
  and cache keys are bit-identical to the synchronous drain with zero
  extra re-traces; a poisoned batch at window position 2 of 3 fails only
  its own riders while earlier/later inflight batches complete.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# Part A: host-side pieces
# ---------------------------------------------------------------------------

def test_tenant_graph_expansion_blocked_layout():
    from repro.serve.batching import split_tenant_states, tenant_graph
    from repro.sparse import datasets
    g = datasets.erdos_renyi(48, avg_degree=4, seed=2)
    T = 3
    tg = tenant_graph(g, T)
    assert tg.n == g.n * T and tg.nnz == g.nnz * T
    rows, cols = tg.row_of(), tg.col_idx.astype(np.int64)
    # every edge stays inside its tenant column (blocked ids: t*n + v)
    assert np.array_equal(rows // g.n, cols // g.n)
    # each column holds exactly the base edge set
    base = set(zip(g.row_of().tolist(), g.col_idx.tolist()))
    for t in range(T):
        sel = rows // g.n == t
        col_edges = set(zip((rows[sel] - t * g.n).tolist(),
                            (cols[sel] - t * g.n).tolist()))
        assert col_edges == base
    # memoized by identity
    assert tenant_graph(g, T) is tg
    # split is the exact inverse of the blocked packing
    state = np.arange(g.n * T, dtype=np.float64)
    parts = split_tenant_states(state, g.n, T)
    for t in range(T):
        assert np.array_equal(parts[t], state[t * g.n:(t + 1) * g.n])


def test_tenant_batch_padding():
    from repro.serve.batching import TenantBatch
    b = TenantBatch(program="bfs", graph="g", width=4, roots=(5, 9),
                    tenants=["a", "b"], req_ids=[1, 2]).padded()
    assert b.roots == (5, 9, 0, 0) and b.n_real == 2
    assert b.req_ids == [1, 2, None, None]
    with pytest.raises(ValueError):
        TenantBatch(program="bfs", graph="g", width=1, roots=(1, 2),
                    tenants=["a", "b"], req_ids=[1, 2]).padded()


def test_queueconfig_round_budget():
    from repro.core.queues import QueueConfig
    assert QueueConfig.from_cap(5, "serve").round_budget("serve", 100, 4) \
        == 20
    # factor sizing: per-channel cap is lane-aligned, budget scales by it
    q = QueueConfig.from_factor(1.0, "serve")
    cap = q.channel_cap("serve", 100, 4)
    assert q.round_budget("serve", 100, 4) == cap * 4
    # unbounded -> no admission limit
    assert QueueConfig.unbounded().round_budget("serve", 100, 4) is None


def test_batched_program_registry():
    from repro.serve.batching import batched_program
    assert batched_program("bfs").init_only == ("roots",)
    assert batched_program("sssp").reduce_op == "min"
    with pytest.raises(KeyError):
        batched_program("pagerank")   # add-reduce: no exact batching


def test_tenant_graph_memo_purges_dead_graphs():
    """The memo must not pin garbage-collected base graphs (unbounded
    growth) — a dead referent's entry disappears with the graph."""
    import gc
    from repro.serve import batching
    from repro.sparse import datasets
    n0 = len(batching._TENANT_GRAPHS)
    g = datasets.erdos_renyi(32, avg_degree=3, seed=4)
    tg = batching.tenant_graph(g, 2)
    assert batching.tenant_graph(g, 2) is tg        # memo hit while alive
    assert len(batching._TENANT_GRAPHS) == n0 + 1
    del g
    gc.collect()
    assert len(batching._TENANT_GRAPHS) == n0


def test_tenant_graph_memo_not_fooled_by_id_reuse():
    """Regression: the memo keyed (id(g), T) alone — once a base CSR was
    collected and a new one landed at the same id, the stale expansion of
    a DIFFERENT graph came back. Simulate the id collision directly: a
    stale entry under g's id whose recorded referent is dead must be
    recomputed, not served."""
    import weakref
    from repro.serve import batching
    from repro.sparse import datasets
    g = datasets.erdos_renyi(32, avg_degree=3, seed=5)
    other = datasets.erdos_renyi(8, avg_degree=2, seed=6)
    stale = batching.tenant_graph(other, 2)

    class _Dead:
        pass

    d = _Dead()
    batching._TENANT_GRAPHS[(id(g), 2)] = (weakref.ref(d), stale)
    del d
    tg = batching.tenant_graph(g, 2)
    assert tg is not stale
    assert tg.n == g.n * 2 and tg.nnz == g.nnz * 2


class _FakeMesh:
    """Just enough mesh for submit-time admission tests (no launches)."""
    devices = np.zeros(4)


def test_submit_rejects_out_of_range_root():
    """Regression: an unvalidated root r >= n (or negative) wraps into
    ANOTHER tenant's column in _multi_root_init, silently corrupting that
    tenant's result. submit() must fail such requests loudly."""
    from repro.serve import ProgramServer, Request, STATUS_FAILED
    from repro.sparse import datasets
    g = datasets.erdos_renyi(32, avg_degree=3, seed=7)
    srv = ProgramServer(_FakeMesh(), {"g": g}, batch_width=2)
    for bad in (g.n, g.n + 5, -1):
        resp = srv.submit(Request(0, "acme", "bfs", "g", root=bad))
        assert resp is not None and resp.status == STATUS_FAILED
        assert "root" in resp.reason and not resp.retriable
    assert srv.queue_depth == 0
    srv.stats.verify()                  # failed roots are all accounted
    assert srv.stats.tenant("acme").failed == 3
    # boundary roots are still admitted
    srv2 = ProgramServer(_FakeMesh(), {"g": g}, batch_width=2)
    assert srv2.submit(Request(1, "acme", "bfs", "g", root=g.n - 1)) is None
    assert srv2.submit(Request(2, "bee", "bfs", "g", root=0)) is None
    assert srv2.queue_depth == 2


def test_multi_root_init_rejects_out_of_range_root():
    """Defense in depth: the init rule itself refuses roots that would
    seed distance 0 outside the request's own tenant column."""
    from repro.serve.batching import tenant_graph
    from repro.sparse import datasets
    from repro.sparse.jax_apps import BATCHED_BFS
    g = datasets.erdos_renyi(16, avg_degree=3, seed=8)
    tg = tenant_graph(g, 2)
    (dist,), _ = BATCHED_BFS.init(tg, {"roots": (0, g.n - 1)})
    assert dist[0] == 0.0 and dist[2 * g.n - 1] == 0.0
    for bad in (g.n, -1):
        with pytest.raises(ValueError, match="out of range"):
            BATCHED_BFS.init(tg, {"roots": (0, bad)})


def test_submit_moe_without_service_fails_accounted():
    """Regression: a 'moe' request on a server with no MoEService raised
    ValueError out of submit(), leaving the request counted as submitted
    but never served/rejected/failed — breaking the stats ledger."""
    from repro.serve import ProgramServer, Request, STATUS_FAILED
    srv = ProgramServer(_FakeMesh(), {})
    resp = srv.submit(Request(0, "acme", "moe",
                              payload=np.zeros((16, 8), np.float32)))
    assert resp is not None and resp.status == STATUS_FAILED
    assert "MoEService" in resp.reason and not resp.retriable
    srv.stats.verify()
    assert srv.stats.tenant("acme").failed == 1


def test_oversized_demand_rejected_nonretriable():
    """Regression: a request whose demand alone exceeds the tenant budget
    was rejected retriable=True with a 'resubmit after drain' reason, so
    a well-behaved retrying client looped forever."""
    from repro.core.queues import QueueConfig
    from repro.serve import ProgramServer, Request, STATUS_REJECTED
    from repro.sparse import datasets
    g = datasets.erdos_renyi(32, avg_degree=3, seed=9)
    srv = ProgramServer(
        _FakeMesh(), {"g": g}, batch_width=2,
        default_queues=QueueConfig.from_cap(2, "serve"))   # budget 8 << nnz
    resp = srv.submit(Request(0, "acme", "bfs", "g", root=0))
    assert resp is not None and resp.status == STATUS_REJECTED
    assert resp.retriable is False
    assert "never" in resp.reason
    srv.stats.verify()
    assert srv.stats.tenant("acme").rejected == 1


def test_serve_options_validation():
    from repro.serve import ServeOptions
    assert ServeOptions().resolve().inflight_depth == 1
    assert ServeOptions(inflight_depth=4, fairness="drr",
                        drr_quantum=100).resolve().fairness == "drr"
    with pytest.raises(ValueError, match="inflight_depth"):
        ServeOptions(inflight_depth=0).resolve()
    with pytest.raises(ValueError, match="fairness"):
        ServeOptions(fairness="lifo").resolve()
    with pytest.raises(ValueError, match="drr_quantum"):
        ServeOptions(drr_quantum=0).resolve()


class _Entry:
    """Former-protocol stub: tenant / klass / demand (+ a test tag)."""

    def __init__(self, tenant, klass, demand=1, tag=0):
        self.tenant, self.klass = tenant, klass
        self.demand, self.tag = demand, tag


def test_fifo_former_head_of_line_scan():
    """FifoFormer is the pre-former serving loop verbatim: the oldest
    request fixes the class, same-class requests from distinct tenants
    ride, everything else keeps arrival order."""
    from repro.serve.batching import FifoFormer
    f = FifoFormer()
    for tenant, klass in [("a", "A"), ("b", "B"), ("c", "A"),
                          ("a", "A"), ("d", "A")]:
        f.push(_Entry(tenant, klass))
    got = f.form(lambda e: 3)
    assert [(e.tenant, e.klass) for e in got] == \
        [("a", "A"), ("c", "A"), ("d", "A")]
    # the duplicate-tenant entry and the off-class entry stay, in order
    assert len(f) == 2 and f.pending_tenants() == ["b", "a"]
    assert [(e.tenant, e.klass) for e in f.form(lambda e: 3)] == [("b", "B")]
    assert [(e.tenant, e.klass) for e in f.form(lambda e: 3)] == [("a", "A")]
    assert f.form(lambda e: 3) == []


def test_drr_former_unstarves_light_tenants():
    """The 1-vs-many skew FIFO gets wrong: a hog with a deep backlog of
    one class vs three light tenants of another. FIFO would serve the
    entire hog backlog first; DRR lets every light tenant set or ride a
    batch within n_tenants formations of arriving."""
    from repro.serve.batching import DrrFormer
    f = DrrFormer()
    for i in range(16):
        f.push(_Entry("hog", ("bfs", "g"), demand=5, tag=i))
    for t in ("lark", "wren", "finch"):
        f.push(_Entry(t, ("sssp", "g"), demand=3))
    batches = []
    while len(f):
        batches.append(f.form(lambda e: 4))
    # formation 1: hog sets (no same-class riders pending); formation 2:
    # the light class launches fused — not after 16 hog batches
    assert [e.tenant for e in batches[0]] == ["hog"]
    assert sorted(e.tenant for e in batches[1]) == ["finch", "lark", "wren"]
    # intra-tenant FIFO: the hog backlog drains in admission order
    hog_tags = [e.tag for b in batches for e in b if e.tenant == "hog"]
    assert hog_tags == list(range(16))


def test_drr_starvation_bound_and_intra_tenant_order():
    """Property pin (the ISSUE acceptance bound): under random mixed
    streams with per-tenant backlog <= batch_width, every admitted
    request launches within ``batch_width * n_tenants`` formations of
    its admission, and each tenant's requests pop in admission order."""
    from repro.serve.batching import DrrFormer
    width = 4
    for seed in range(8):
        rng = np.random.default_rng(seed)
        n_tenants = int(rng.integers(2, 6))
        tenants = [f"t{i}" for i in range(n_tenants)]
        classes = [("bfs", "g"), ("sssp", "g"), ("bfs", "h")]
        f = DrrFormer()
        formations, tag = 0, 0
        admitted_at = {}
        pending = {t: 0 for t in tenants}
        pushed = {t: [] for t in tenants}
        popped = {t: [] for t in tenants}

        def push_some():
            nonlocal tag
            for t in tenants:
                for _ in range(int(rng.integers(0, width + 1 - pending[t]))):
                    f.push(_Entry(t, classes[int(rng.integers(0, 3))],
                                  demand=int(rng.integers(1, 9)), tag=tag))
                    admitted_at[tag] = formations
                    pushed[t].append(tag)
                    pending[t] += 1
                    tag += 1

        push_some()
        while len(f):
            batch = f.form(lambda e: width)
            formations += 1
            assert batch and len({e.tenant for e in batch}) == len(batch)
            assert len({e.klass for e in batch}) == 1
            for e in batch:
                popped[e.tenant].append(e.tag)
                pending[e.tenant] -= 1
                wait = formations - admitted_at[e.tag]
                assert wait <= width * n_tenants, (seed, e.tag, wait)
            if rng.random() < 0.3:
                push_some()
        for t in tenants:
            assert popped[t] == pushed[t], (seed, t)


def test_stats_reservoirs_bounded():
    """A resident server runs for days: every per-event reservoir is a
    bounded deque so host memory stays O(STATS_WINDOW) — this test pins
    the cap and the over-the-window eviction behavior."""
    from repro.serve.stats import STATS_WINDOW, ServingStats, TenantStats
    assert STATS_WINDOW == 4096                   # the documented cap
    ts = TenantStats()
    for i in range(STATS_WINDOW + 123):
        ts.latencies.append(float(i))
        ts.queue_waits.append(float(i))
        ts.device_times.append(float(i))
    assert ts.latencies.maxlen == STATS_WINDOW
    assert len(ts.latencies) == len(ts.queue_waits) \
        == len(ts.device_times) == STATS_WINDOW
    # quantiles cover the most recent window only (oldest 123 evicted)
    assert ts.snapshot()["p50_latency_s"] >= 123
    ss = ServingStats()
    for d in range(STATS_WINDOW + 7):
        ss.observe_queue_depth(d)
        ss.round_latencies.append(float(d))
    assert len(ss.queue_depth_samples) == len(ss.round_latencies) \
        == STATS_WINDOW
    assert min(ss.queue_depth_samples) == 7       # eviction really happened
    # ... but the running max survives the window
    assert ss.max_queue_depth == STATS_WINDOW + 6
    assert ss.snapshot()["max_queue_depth"] == STATS_WINDOW + 6


# ---------------------------------------------------------------------------
# Part B: the serving contract under shard_map (subprocess)
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import dataclasses
import json
import numpy as np
import jax
from repro.core.compat import make_mesh
from repro.core.queues import QueueConfig
from repro.sparse import datasets, program
from repro.sparse.jax_apps import BFS, SSSP
from repro.sparse.program import run_program
from repro.sparse.options import LaunchOptions
from repro.serve import (MoEService, ProgramServer, Request,
                         STATUS_OK, STATUS_REJECTED)

res = {}
g = datasets.wiki_like(192, avg_degree=6, seed=3)
mesh = make_mesh((4,), ('data',))
WIDTH = 4

# ---- pre-warm populates exactly the expected keys ----------------------
program.clear_cache()
srv = ProgramServer(mesh, {'wiki': g}, batch_width=WIDTH)
warm = srv.prewarm(('bfs', 'sssp'))
res['warm'] = {'keys_per_class': {f'{p}/{gn}': len(ks)
                                  for (p, gn), ks in warm.items()},
               'cache': program.cache_stats(),
               'n_cache_keys': len(program.cache_keys())}
warm2 = srv.prewarm(('bfs', 'sssp'))      # idempotent: nothing new
res['warm_again'] = {'new_keys': sum(len(k) for k in warm2.values()),
                     'cache': program.cache_stats()}

# ---- mixed 4-tenant x 2-program stream under serving load --------------
TENANTS = ['acme', 'globex', 'initech', 'umbrella']
reqs = [Request(i, TENANTS[i % 4], 'bfs' if i % 2 == 0 else 'sssp',
                'wiki', root=(i * 13) % g.n) for i in range(16)]
c0 = program.cache_stats()
resps = srv.run(reqs)
c1 = program.cache_stats()
res['stream'] = {
    'statuses': [r.status for r in resps],
    'new_hits': c1['hits'] - c0['hits'],
    'new_misses': c1['misses'] - c0['misses'],
    'new_traces': c1['kernel_traces'] - c0['kernel_traces'],
    'identical': [], 'drops': sum(r.batch_drops for r in resps)}
for r, resp in zip(reqs, resps):
    prog = BFS if r.program == 'bfs' else SSSP
    (d,), _ = run_program(prog, g, mesh, params={'root': r.root})
    res['stream']['identical'].append(
        bool(np.array_equal(d, resp.result)))
srv.stats.verify()
res['stats'] = srv.stats.snapshot()

# ---- continuous serving: depth sweep bit-identity + zero re-traces -----
from repro.serve import ServeOptions

def _sig(rs):
    return [(r.req_id, r.tenant, r.status, r.retriable, r.reason,
             None if r.result is None else r.result.tobytes(),
             r.batch_drops, r.batch_messages, r.rounds, r.batch_width)
            for r in sorted(rs, key=lambda r: r.req_id)]

def _ledger(s):
    return {t: (v.submitted, v.served, v.rejected, v.failed)
            for t, v in s.stats.tenants.items()}

base_sig = _sig(resps)          # the depth-1 FIFO synchronous drain
base_ledger = _ledger(srv)
res['depths'] = {}
for depth, fairness in [(1, 'fifo'), (2, 'fifo'), (4, 'fifo'), (3, 'drr')]:
    c0 = program.cache_stats()
    srv_d = ProgramServer(mesh, {'wiki': g}, batch_width=WIDTH,
                          serve_options=ServeOptions(inflight_depth=depth,
                                                     fairness=fairness))
    rs = srv_d.run(reqs)
    c1 = program.cache_stats()
    srv_d.stats.verify()
    res['depths'][f'{fairness}{depth}'] = {
        'sig_equal': _sig(rs) == base_sig,
        'ledger_equal': _ledger(srv_d) == base_ledger,
        'new_misses': c1['misses'] - c0['misses'],
        'new_traces': c1['kernel_traces'] - c0['kernel_traces'],
        'launches': srv_d.stats.launches}

# ---- donated buffers: own key class, still bit-identical ---------------
srv_don = ProgramServer(mesh, {'wiki': g}, batch_width=WIDTH,
                        serve_options=ServeOptions(inflight_depth=3,
                                                   donate_buffers=True))
k0 = len(program.cache_keys())
srv_don.prewarm(('bfs', 'sssp'))
k1 = len(program.cache_keys())
c0 = program.cache_stats()
rs_don = srv_don.run(reqs)
c1 = program.cache_stats()
srv_don.stats.verify()
res['donate'] = {'sig_equal': _sig(rs_don) == base_sig,
                 'new_keys_prewarm': k1 - k0,
                 'new_misses_under_load': c1['misses'] - c0['misses'],
                 'new_traces_under_load':
                     c1['kernel_traces'] - c0['kernel_traces']}

# ---- failure in flight: poisoned batch at window position 2 of 3 -------
POISON_ROOT = g.n - 1
real_launch = program.launch_program
window_at_launch = []
def _poisoned(prog, data, fabric, **kw):
    window_at_launch.append(srv_f.inflight_depth)
    roots = tuple((kw.get('params') or {}).get('roots') or ())
    if POISON_ROOT in roots:
        raise RuntimeError('injected launch failure')
    return real_launch(prog, data, fabric, **kw)
program.launch_program = _poisoned
try:
    srv_f = ProgramServer(mesh, {'wiki': g}, batch_width=WIDTH,
                          serve_options=ServeOptions(inflight_depth=3))
    f_reqs = (
        [Request(i, f'a{i}', 'bfs', 'wiki', root=1) for i in range(4)]
        + [Request(4 + i, f'b{i}', 'bfs', 'wiki',
                   root=POISON_ROOT if i == 0 else 2) for i in range(4)]
        + [Request(8 + i, f'c{i}', 'bfs', 'wiki', root=3) for i in range(4)])
    f_resps = srv_f.run(f_reqs)
    srv_f.stats.verify()
finally:
    program.launch_program = real_launch
(ok1,), _ = run_program(BFS, g, mesh, params={'root': 1})
(ok3,), _ = run_program(BFS, g, mesh, params={'root': 3})
res['failure'] = {
    'n_responses': len(f_resps),
    'statuses': [r.status for r in f_resps],
    'retriable': [r.retriable for r in f_resps],
    'reasons_failed': [r.reason for r in f_resps if r.status != STATUS_OK],
    'survivors_identical': bool(
        np.array_equal(f_resps[0].result, ok1)
        and np.array_equal(f_resps[8].result, ok3)),
    'max_window_at_launch': max(window_at_launch),
    'ledger': _ledger(srv_f)}

# ---- admission control: undersized per-tenant budget -------------------
n_dev = 4
one_req = QueueConfig.from_cap(g.nnz // n_dev + 1, 'serve')
tiny = QueueConfig.from_cap(2, 'serve')
srv2 = ProgramServer(mesh, {'wiki': g}, batch_width=WIDTH,
                     tenant_queues={'acme': one_req, 'globex': tiny})
r_ok = srv2.submit(Request(0, 'acme', 'bfs', 'wiki', root=1))
r_over = srv2.submit(Request(1, 'acme', 'bfs', 'wiki', root=2))
r_tiny = srv2.submit(Request(2, 'globex', 'bfs', 'wiki', root=3))
drained = srv2.drain()
r_retry = srv2.submit(Request(3, 'acme', 'bfs', 'wiki', root=2))
drained += srv2.drain()
srv2.stats.verify()
res['admission'] = {
    'first_admitted': r_ok is None,
    'over_budget': None if r_over is None else
        {'status': r_over.status, 'retriable': r_over.retriable},
    'tiny_budget': None if r_tiny is None else
        {'status': r_tiny.status, 'retriable': r_tiny.retriable},
    'retry_after_drain_admitted': r_retry is None,
    'served': [r.status for r in drained],
    'tenant_stats': srv2.stats.snapshot()['tenants']}

# ---- undersized LAUNCH queues: drops are attributed, never silent ------
srv3 = ProgramServer(mesh, {'wiki': g}, batch_width=WIDTH,
                     options=LaunchOptions(
                         queues=QueueConfig.from_cap(2, 'T3')))
resp3 = srv3.run([Request(i, f't{i}', 'bfs', 'wiki', root=i)
                  for i in range(2)])
srv3.stats.verify()
res['drops'] = {'batch_drops': [r.batch_drops for r in resp3],
                'stats_drops': srv3.stats.noc_drops,
                'statuses': [r.status for r in resp3]}

# ---- MoE lane: batched dispatch, warm after one trace ------------------
from repro.configs import get_config
from repro.core.dispatch import MeshInfo
from repro.models.moe import init_moe, moe_einsum
cfg = get_config('olmoe-1b-7b').reduced()
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, capacity_factor=8.0))
params = init_moe(jax.random.key(0), cfg)
mesh2 = make_mesh((2, 2, 2), ('data', 'expert', 'tp'))
moe = MoEService(cfg, params, MeshInfo(mesh2, pod_axis=None),
                 batch=4, seq=16)
srv4 = ProgramServer(mesh2, {}, moe=moe)
srv4.prewarm(('moe',))
traces_after_warm = moe.traces
rng = np.random.default_rng(0)
blocks = [rng.normal(size=(16, cfg.d_model)).astype(np.float32)
          for _ in range(6)]
mreqs = [Request(i, f'm{i % 3}', 'moe', payload=b)
         for i, b in enumerate(blocks)]
mresps = srv4.run(mreqs)
srv4.stats.verify()
x = np.zeros((4, 16, cfg.d_model), np.float32)
for i in range(4):
    x[i] = blocks[i]
oracle, _ = moe_einsum(params, x, cfg)
err = max(float(np.max(np.abs(np.asarray(oracle)[i] - mresps[i].result)))
          for i in range(4))
res['moe'] = {'statuses': [r.status for r in mresps],
              'traces_after_warm': traces_after_warm,
              'traces_final': moe.traces, 'calls': moe.calls,
              'oracle_err': err,
              'cache_hits': srv4.stats.cache_hits,
              'cache_misses': srv4.stats.cache_misses}
print('RESULT ' + json.dumps(res))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_prewarm_populates_exactly_the_expected_keys(results):
    w = results["warm"]
    # one shape class per (program, graph, batch width) -> one key each
    assert w["keys_per_class"] == {"bfs/wiki": 1, "sssp/wiki": 1}
    assert w["n_cache_keys"] == 2
    assert w["cache"]["misses"] == 2
    assert w["cache"]["kernel_traces"] == 2
    # idempotent: a second pre-warm adds nothing and re-traces nothing
    again = results["warm_again"]
    assert again["new_keys"] == 0
    assert again["cache"]["misses"] == 2
    assert again["cache"]["kernel_traces"] == 2


def test_stream_serves_all_tenants_ok(results):
    s = results["stream"]
    assert s["statuses"] == ["ok"] * 16
    assert s["drops"] == 0


def test_results_bit_identical_to_standalone_runs(results):
    assert all(results["stream"]["identical"])


def test_serving_load_is_cache_hits_only(results):
    """The cache_stats()/_cached contract under a mixed request stream:
    after pre-warm, repeated mixed-program batches must be hits — no new
    misses and, critically, zero new jit traces."""
    s = results["stream"]
    assert s["new_hits"] >= 4          # 16 reqs / width 4 = 4+ launches
    assert s["new_misses"] == 0
    assert s["new_traces"] == 0
    stats = results["stats"]
    assert stats["cache_hit_rate"] == 1.0
    assert stats["launches"] >= 4
    assert stats["batched_requests"] == 16


def test_stats_snapshot_shape(results):
    stats = results["stats"]
    assert set(stats["tenants"]) == {"acme", "globex", "initech",
                                     "umbrella"}
    for ts in stats["tenants"].values():
        assert ts["submitted"] == ts["served"] == 4
        assert ts["p50_latency_s"] <= ts["p99_latency_s"]
        assert ts["rounds"] > 0 and ts["messages"] > 0
    assert stats["max_queue_depth"] >= 1
    assert stats["p50_round_latency_s"] <= stats["p99_round_latency_s"]
    assert stats["noc_drops"] == 0


def test_admission_rejects_retriably_not_silently(results):
    a = results["admission"]
    assert a["first_admitted"]
    assert a["over_budget"] == {"status": "rejected", "retriable": True}
    # globex's budget can't fit the request even when idle: rejecting it
    # retriable would send a well-behaved client into a futile retry loop
    assert a["tiny_budget"] == {"status": "rejected", "retriable": False}
    assert a["retry_after_drain_admitted"]
    assert a["served"] == ["ok", "ok"]
    # the ledger balances: every submit is served or rejected
    acme = a["tenant_stats"]["acme"]
    assert acme["submitted"] == 3 and acme["served"] == 2
    assert acme["rejected"] == 1
    globex = a["tenant_stats"]["globex"]
    assert globex["submitted"] == 1 and globex["rejected"] == 1


def test_launch_queue_drops_are_attributed(results):
    d = results["drops"]
    assert d["statuses"] == ["ok", "ok"]
    assert d["stats_drops"] > 0                    # tight cap really drops
    assert all(b == d["stats_drops"] for b in d["batch_drops"])


def test_moe_lane_warm_after_one_trace(results):
    m = results["moe"]
    assert m["statuses"] == ["ok"] * 6
    assert m["traces_after_warm"] == 1
    assert m["traces_final"] == 1                  # no re-trace under load
    assert m["calls"] == 3                         # warm + 2 batches
    assert m["oracle_err"] < 1e-5
    assert m["cache_hits"] == 2 and m["cache_misses"] == 0


def test_moe_lane_batches_by_fixed_width(results):
    # 6 single-block requests from 3 tenants -> two fused launches of the
    # fixed [4, 16, D] shape class (max one request per tenant per batch)
    assert results["moe"]["calls"] - 1 == 2


def test_depth_sweep_bit_identical_to_sync_drain(results):
    """The ISSUE acceptance gate: for inflight_depth in {1, 2, 4} (FIFO)
    and depth 3 under DRR, the full response signature (results, statuses,
    reasons, batch attribution) and the per-tenant ledger are bit-identical
    to the synchronous drain — and the overlapped window re-uses the very
    same compile-cache entries: zero new misses, zero new jit traces."""
    depths = results["depths"]
    assert set(depths) == {"fifo1", "fifo2", "fifo4", "drr3"}
    for name, leg in depths.items():
        assert leg["sig_equal"], name
        assert leg["ledger_equal"], name
        assert leg["new_misses"] == 0, name      # byte-compatible keys
        assert leg["new_traces"] == 0, name
        assert leg["launches"] >= 4, name


def test_donated_buffers_own_key_class_same_responses(results):
    """donate_argnums changes lowering, so donation joins the cache key —
    exactly one new key per pre-warmed shape class, none for the default
    path — and the donated pipeline still serves bit-identical responses
    with zero re-traces after its pre-warm."""
    d = results["donate"]
    assert d["new_keys_prewarm"] == 2            # donated bfs + sssp
    assert d["sig_equal"]
    assert d["new_misses_under_load"] == 0
    assert d["new_traces_under_load"] == 0


def test_failure_in_flight_poisons_only_its_batch(results):
    """A launch failure at window position 2 of 3 (inflight_depth=3)
    fails only its own riders — non-retriably — while the earlier and
    later inflight batches complete bit-identically; every response is
    delivered exactly once and the ledger balances."""
    f = results["failure"]
    assert f["n_responses"] == 12                # nothing dropped/doubled
    assert f["statuses"] == ["ok"] * 4 + ["failed"] * 4 + ["ok"] * 4
    assert f["retriable"] == [False] * 12
    assert len(f["reasons_failed"]) == 4
    assert all("injected launch failure" in r for r in f["reasons_failed"])
    assert f["survivors_identical"]
    # the poisoned launch really was issued with 2 batches already in
    # flight (window positions fill 0, 1, 2 before any harvest)
    assert f["max_window_at_launch"] == 2
    # ledger rows are (submitted, served, rejected, failed)
    for tenant, row in f["ledger"].items():
        want = [1, 0, 0, 1] if tenant.startswith("b") else [1, 1, 0, 0]
        assert row == want, (tenant, row)
