"""The TaskProgram runtime (:mod:`repro.sparse.program`).

Part A — in-process properties: the vectorised edge packer matches a
per-device reference, the owner layout round-trips.

Part B (subprocess, 8 fake host devices) — the analytic-twin contract:
for EVERY program (all seven apps) on 1/2/4/8 devices, the executable's
per-round message/drop trajectory must equal the twin's
(``program_app_stats`` replaying the generated task stream through
``TaskEngine.route``), with tight explicit caps actually dropping; the
pod/portal path agrees against the two-stage channel mirror; k-core (the
seventh app, a pure program definition) matches its numpy oracle with a
partial peel; and repeated same-shape launches hit the compile cache
without re-tracing.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

APPS = ("bfs", "sssp", "wcc", "pagerank", "kcore", "spmv", "histogram")
DEVS = (1, 2, 4, 8)


# ---------------------------------------------------------------------------
# Part A: host-side pieces
# ---------------------------------------------------------------------------

def _pack_edges_reference(rows, cols, wts, n_dev, seed=0):
    """The pre-vectorisation per-device packer (kept as the oracle)."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(rows))
    rows, cols, wts = rows[perm], cols[perm], wts[perm]
    own = (rows % n_dev).astype(np.int64)
    counts = np.bincount(own, minlength=n_dev)
    E_max = max(8, int(counts.max()))
    src_slot = np.zeros((n_dev, E_max), np.int32)
    dst = np.full((n_dev, E_max), -1, np.int32)
    w = np.zeros((n_dev, E_max), np.float32)
    for d in range(n_dev):
        sel = own == d
        k = int(counts[d])
        src_slot[d, :k] = (rows[sel] // n_dev).astype(np.int32)
        dst[d, :k] = cols[sel].astype(np.int32)
        w[d, :k] = wts[sel]
    return (src_slot.reshape(-1), dst.reshape(-1), w.reshape(-1), E_max)


@pytest.mark.parametrize("n_dev", [1, 3, 8])
@pytest.mark.parametrize("seed", [0, 7])
def test_pack_edges_matches_per_device_reference(n_dev, seed):
    from repro.sparse.program import _pack_edges
    rng = np.random.default_rng(seed + 100)
    E, n = 500, 64
    rows = rng.integers(0, n, E)
    cols = rng.integers(0, n, E)
    wts = rng.random(E).astype(np.float32)
    got = _pack_edges(rows, cols, wts, n_dev, seed)
    want = _pack_edges_reference(rows, cols, wts, n_dev, seed)
    assert got[3] == want[3]
    for g_arr, w_arr in zip(got[:3], want[:3]):
        assert np.array_equal(np.asarray(g_arr), w_arr)


def test_pack_edges_empty():
    from repro.sparse.program import _pack_edges
    e = np.array([], np.int64)
    src_slot, dst, w, E_max = _pack_edges(e, e, e.astype(np.float32), 4)
    assert E_max == 8 and (np.asarray(dst) == -1).all()


def test_owner_layout_round_trips():
    from repro.sparse.program import from_owner_layout, owner_layout
    rng = np.random.default_rng(3)
    for n, n_dev in ((17, 4), (32, 8), (5, 8)):
        arr = rng.random(n)
        packed, valid = owner_layout(arr, n_dev)
        assert int(np.asarray(valid).sum()) == n
        back = np.asarray(from_owner_layout(packed, n, n_dev))
        assert np.allclose(back, arr)


# ---------------------------------------------------------------------------
# Part B: the analytic-twin contract under shard_map (subprocess)
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import numpy as np
from repro.core.compat import make_mesh
from repro.sparse import datasets, program, ref
from repro.sparse.jax_apps import PROGRAMS, dcra_bfs, dcra_kcore
from repro.sparse.program import program_app_stats, run_program
from repro.sparse.options import LaunchOptions

g = datasets.wiki_like(256, avg_degree=8, seed=7)
x = np.random.default_rng(0).random(g.n)
els = datasets.histogram_data(1 << 11, 64, seed=4)
PARAMS = {'bfs': {'root': 0}, 'sssp': {'root': 0}, 'wcc': {},
          'pagerank': {'damping': 0.85, 'iters': 4}, 'kcore': {'k': 8.0},
          'spmv': {}, 'histogram': {}}
DATA = {'spmv': (g, x), 'histogram': (els, 64)}

res = {'parity': [], 'pod': [], 'cache': {}, 'results': {}}

def parity_case(app, n_dev, tag, stats, twin):
    return {'app': app, 'n_dev': n_dev, 'cap': tag,
            'ok': (stats.rounds == twin.rounds
                   and np.array_equal(stats.messages, twin.messages)
                   and np.array_equal(stats.drops, twin.drops)),
            'rounds': stats.rounds, 'msgs': stats.total_messages,
            'drops': stats.total_drops,
            'twin_drops': twin.total_drops}

for n_dev in (1, 2, 4, 8):
    mesh = make_mesh((n_dev,), ('data',))
    for app, prog in PROGRAMS.items():
        data = DATA.get(app, g)
        caps = (2, 96) if n_dev in (1, 8) else (2,)
        for cap in caps:
            _, stats = run_program(prog, data, mesh,
                                   options=LaunchOptions(cap=cap),
                                   params=PARAMS[app])
            twin = program_app_stats(prog, data, n_dev, cap=cap,
                                     params=PARAMS[app])
            res['parity'].append(parity_case(app, n_dev, cap, stats, twin))

# ---- pod/portal path: two-stage channel mirror (every program) ----
hier = make_mesh((2, 4), ('pod', 'data'))
for app, prog in PROGRAMS.items():
    data = DATA.get(app, g)
    for cf in (0.25, 4.0):
        _, stats = run_program(prog, data, hier,
                               options=LaunchOptions(pod_axis='pod',
                                                     capacity_factor=cf),
                               params=PARAMS[app])
        twin = program_app_stats(prog, data, 8, capacity_factor=cf,
                                 params=PARAMS[app], pods=(4, 2))
        res['pod'].append(parity_case(app, 8, f'cf{cf}', stats, twin))

# ---- the seventh app vs its oracle (flat + pod, drop-free sizing) ----
mesh8 = make_mesh((8,), ('data',))
k_, st = dcra_kcore(g, 8, mesh8)
want = ref.kcore_ref(g, 8)
res['results']['kcore'] = {
    'err': int(np.abs(k_ - want).max()),
    'drops': st.total_drops, 'rounds': st.rounds,
    'partial_peel': bool(0 < int((k_ >= 0).sum()) < g.n)}
k2, _ = dcra_kcore(g, 8, hier, options=LaunchOptions(pod_axis='pod'))
res['results']['kcore_pod_err'] = int(np.abs(k2 - want).max())
d_, st = dcra_bfs(g, 0, hier, options=LaunchOptions(pod_axis='pod'))
res['results']['bfs_pod'] = {
    'err': int(np.abs(d_ - ref.bfs_ref(g, 0)).max()),
    'drops': st.total_drops}

# ---- compile cache: repeated same-shape launches must not re-trace ----
program.clear_cache()
dcra_bfs(g, 0, mesh8)
s1 = program.cache_stats()
dcra_bfs(g, 0, mesh8)
s2 = program.cache_stats()
dcra_bfs(g, 0, make_mesh((4,), ('data',)))
s3 = program.cache_stats()
res['cache'] = {'first': s1, 'repeat': s2, 'other_mesh': s3}
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


def test_parity_covers_every_app_and_device_count(results):
    seen = {(c["app"], c["n_dev"]) for c in results["parity"]}
    assert seen == {(a, d) for a in APPS for d in DEVS}


@pytest.mark.parametrize("app", APPS)
def test_analytic_twin_matches_executable(results, app):
    cases = [c for c in results["parity"] if c["app"] == app]
    bad = [c for c in cases if not c["ok"]]
    assert not bad, bad


@pytest.mark.parametrize("app", APPS)
def test_tight_caps_actually_drop(results, app):
    """cap=2 must overflow for every app, or the agreement is vacuous."""
    tight = [c for c in results["parity"]
             if c["app"] == app and c["cap"] == 2]
    assert any(c["drops"] > 0 for c in tight), tight


def test_pod_portal_path_agrees_with_two_stage_mirror(results):
    assert {c["app"] for c in results["pod"]} == set(APPS)
    bad = [c for c in results["pod"] if not c["ok"]]
    assert not bad, bad
    assert any(c["drops"] > 0 for c in results["pod"])   # tight factor
    assert any(c["drops"] == 0 for c in results["pod"])  # roomy factor


def test_kcore_matches_oracle_with_partial_peel(results):
    r = results["results"]["kcore"]
    assert r["err"] == 0 and r["drops"] == 0
    assert r["partial_peel"] and r["rounds"] > 1
    assert results["results"]["kcore_pod_err"] == 0


def test_iterative_app_runs_hierarchically(results):
    r = results["results"]["bfs_pod"]
    assert r["err"] == 0 and r["drops"] == 0


def test_repeated_launches_hit_the_compile_cache(results):
    first = results["cache"]["first"]
    repeat = results["cache"]["repeat"]
    other = results["cache"]["other_mesh"]
    assert repeat["hits"] == first["hits"] + 1
    assert repeat["misses"] == first["misses"]
    # no re-trace on the cache hit
    assert repeat["kernel_traces"] == first["kernel_traces"]
    # a different deployment is a genuine miss, not a stale reuse
    assert other["misses"] == repeat["misses"] + 1


# ---------------------------------------------------------------------------
# Part C: resident packed graphs (subprocess, 8 fake host devices)
# ---------------------------------------------------------------------------

RESIDENT_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import gc
import json
import jax
import numpy as np
from repro.core.fabric import Fabric
from repro.sparse import datasets, program
from repro.sparse.csr import CSR
from repro.sparse.jax_apps import BFS, PAGERANK, SSSP, WCC
from repro.sparse.program import (launch_program, program_app_stats,
                                  run_program)
from repro.sparse.options import LaunchOptions

g = datasets.rmat(8, edge_factor=8, seed=5)
FABRICS = {'one': Fabric.single((1,), ('data',)), 'fake4': Fabric.fake(4)}
ROOTS = [int(v) for v in np.argsort(-g.degrees(), kind='stable')[:3]]
APPS = {'bfs': (BFS, [{'root': r} for r in ROOTS]),
        'pagerank': (PAGERANK, [{'damping': 0.85, 'iters': 4}] * 3)}


def same(a, b):
    (sa, ta), (sb, tb) = a, b
    return (len(sa) == len(sb)
            and all(x.tobytes() == y.tobytes() for x, y in zip(sa, sb))
            and ta.rounds == tb.rounds
            and np.array_equal(ta.messages, tb.messages)
            and np.array_equal(ta.drops, tb.drops))


def copy_of(h):
    return CSR(h.row_ptr.copy(), h.col_idx.copy(), h.values.copy())


def packs(fn):
    before = program.cache_stats()['graph_packs']
    out = fn()
    return program.cache_stats()['graph_packs'] - before, out


res = {'resident': {}, 'repack': {}, 'twin': {}}

# ---- N launches on one graph: 1 pack, N - 1 hits, cold-pack results ----
for fname, fab in FABRICS.items():
    for app, (prog, plist) in APPS.items():
        cold = []
        for p in plist:
            program.clear_cache()
            cold.append(run_program(prog, g, fab, params=p))
        program.clear_cache()
        warm = [launch_program(prog, g, fab, params=plist[0]).result()]
        c1 = program.cache_stats()
        warm += [launch_program(prog, g, fab, params=p).result()
                 for p in plist[1:]]
        c = program.cache_stats()
        res['resident'][f'{fname}-{app}'] = {
            'n': len(plist), 'packs': c['graph_packs'],
            'hits': c['graph_pack_hits'],
            'traces_on_hits': c['kernel_traces'] - c1['kernel_traces'],
            'identical': all(same(a, b) for a, b in zip(cold, warm))}

# ---- what packs again ---------------------------------------------------
one = FABRICS['one']
program.clear_cache()
h = copy_of(g)
run_program(BFS, h, one, params={'root': 0})
rp = res['repack']
rp['same'] = packs(lambda: run_program(BFS, h, one, params={'root': 1}))[0]
rp['new_csr'] = packs(lambda: run_program(
    BFS, CSR(h.row_ptr, h.col_idx, h.values), one, params={'root': 0}))[0]
rp['seed'] = packs(lambda: run_program(BFS, h, one, params={'root': 0},
                                       options=LaunchOptions(seed=1)))[0]
rp['undirected'] = packs(lambda: run_program(WCC, h, one))[0]
rp['fabric'] = packs(lambda: run_program(BFS, h, FABRICS['fake4'],
                                         params={'root': 0}))[0]
# the same device count on other devices: one host pack, placed twice
other4 = Fabric.single((4,), ('data',), devices=jax.devices()[4:8])
n_other, got = packs(lambda: run_program(BFS, h, other4,
                                         params={'root': 0}))
res['other_devices'] = {
    'packs': n_other,
    'placements': len(program.packed_graph(h, 4)._placed),
    'identical': same(got, run_program(BFS, copy_of(h), FABRICS['fake4'],
                                       params={'root': 0}))}
run_program(SSSP, h, one, params={'root': 0})
h.values = h.values * 3.0
n_re, got = packs(lambda: run_program(SSSP, h, one, params={'root': 0}))
rp['reassigned_field'] = n_re
rp['reassigned_fresh'] = same(got, run_program(SSSP, copy_of(h), one,
                                               params={'root': 0}))

# ---- read-only once packed ----------------------------------------------
res['read_only'] = {}
for field in ('row_ptr', 'col_idx', 'values'):
    try:
        getattr(h, field)[0] = 1
        res['read_only'][field] = False
    except ValueError:
        res['read_only'][field] = True

# ---- memo lifetime ------------------------------------------------------
h3 = copy_of(g)
run_program(BFS, h3, one, params={'root': 0})
k3 = id(h3)
had = any(key[0] == k3 for key in program._PACKED)
del h3
gc.collect()
res['lifetime'] = {'collected': had and not any(key[0] == k3
                                                for key in program._PACKED)}
had = len(program._PACKED) > 0
program.clear_cache()
res['lifetime']['cleared'] = had and not program._PACKED

# ---- the analytic twin reads the launch's handle ------------------------
fab4 = FABRICS['fake4']
for app, (prog, plist) in APPS.items():
    p = plist[0]
    program.clear_cache()
    cold = program_app_stats(prog, g, 4, cap=2, params=p)
    program.clear_cache()
    _, st = run_program(prog, g, fab4, options=LaunchOptions(cap=2), params=p)
    c0 = program.cache_stats()
    twin = program_app_stats(prog, g, 4, cap=2, params=p)
    c1 = program.cache_stats()
    res['twin'][app] = {
        'unchanged': same(((), cold), ((), twin)),
        'matches_launch': same(((), st), ((), twin)),
        'drops': twin.total_drops,
        'new_packs': c1['graph_packs'] - c0['graph_packs'],
        'new_hits': c1['graph_pack_hits'] - c0['graph_pack_hits']}
print('RESULT ' + json.dumps(res))
"""


@pytest.fixture(scope="module")
def resident():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", RESIDENT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("fabric", ["one", "fake4"])
@pytest.mark.parametrize("app", ["bfs", "pagerank"])
def test_launches_on_one_graph_pack_once(resident, fabric, app):
    """N launches: one pack, N - 1 hits, no re-trace, and every state and
    AppStats bit-identical to a launch that packed cold."""
    r = resident["resident"][f"{fabric}-{app}"]
    assert r["packs"] == 1
    assert r["hits"] == r["n"] - 1
    assert r["traces_on_hits"] == 0
    assert r["identical"]


@pytest.mark.parametrize("change", ["new_csr", "reassigned_field", "seed",
                                    "undirected", "fabric"])
def test_a_changed_input_packs_again(resident, change):
    rp = resident["repack"]
    assert rp["same"] == 0
    assert rp[change] == 1
    if change == "reassigned_field":
        assert rp["reassigned_fresh"]       # the new weights, not stale


def test_other_devices_reuse_the_host_pack(resident):
    r = resident["other_devices"]
    assert r["packs"] == 0 and r["placements"] == 2 and r["identical"]


@pytest.mark.parametrize("field", ["row_ptr", "col_idx", "values"])
def test_in_place_write_to_a_launched_graph_raises(resident, field):
    assert resident["read_only"][field]


@pytest.mark.parametrize("how", ["collected", "cleared"])
def test_memo_holds_no_dead_or_cleared_graph(resident, how):
    assert resident["lifetime"][how]


@pytest.mark.parametrize("app", ["bfs", "pagerank"])
def test_twin_reuses_the_launch_handle(resident, app):
    t = resident["twin"][app]
    assert t["unchanged"] and t["matches_launch"]
    assert t["new_packs"] == 0 and t["new_hits"] == 1
    if app == "bfs":
        assert t["drops"] > 0               # cap=2 bites: not vacuous
