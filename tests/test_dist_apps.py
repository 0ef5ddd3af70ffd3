"""Differential tests: all SEVEN apps (the paper's six + k-core) on the
distributed owner-routed path vs the numpy oracles in ``sparse/ref.py``.

Coverage matrix (subprocess, 8 fake host devices):
  * Erdős–Rényi + power-law (wiki-like) graphs, 8 devices, all apps;
  * a disconnected graph for BFS (unreachable -> -1) and WCC (two
    components keep distinct labels);
  * a second device count (4) over ER for all apps — the result must
    be layout-independent.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import numpy as np
from repro.core.compat import make_mesh
from repro.sparse import datasets, ref
from repro.sparse.jax_apps import (dcra_bfs, dcra_histogram, dcra_kcore,
                                   dcra_pagerank, dcra_spmv, dcra_sssp,
                                   dcra_wcc)
from repro.sparse.options import LaunchOptions

def run_six(g, mesh, tag, res):
    x = np.random.default_rng(0).random(g.n)
    y, drops = dcra_spmv(g, x, mesh,
                         options=LaunchOptions(capacity_factor=3.0))
    res[f'{tag}/spmv'] = {
        'err': float(np.max(np.abs(np.asarray(y) - ref.spmv_ref(g, x)))
                     / max(1.0, float(np.abs(ref.spmv_ref(g, x)).max()))),
        'drops': int(drops), 'rounds': 1}
    els = datasets.histogram_data(1 << 12, 64, seed=4)
    h, d = dcra_histogram(els, 64, mesh,
                          options=LaunchOptions(capacity_factor=3.0))
    res[f'{tag}/histogram'] = {
        'err': float(np.max(np.abs(np.asarray(h) -
                                   ref.histogram_ref(els, 64)))),
        'drops': int(d), 'rounds': 1}
    d_, st = dcra_bfs(g, 0, mesh)
    res[f'{tag}/bfs'] = {
        'err': float(np.max(np.abs(d_ - ref.bfs_ref(g, 0)))),
        'drops': st.total_drops, 'rounds': st.rounds,
        'messages': st.total_messages}
    s_, st = dcra_sssp(g, 0, mesh)
    want = ref.sssp_ref(g, 0)
    both = np.where(np.isfinite(want), np.abs(s_ - want),
                    (~np.isinf(s_)).astype(float))
    res[f'{tag}/sssp'] = {'err': float(np.max(both)),
                          'drops': st.total_drops, 'rounds': st.rounds}
    p_, st = dcra_pagerank(g, mesh)
    res[f'{tag}/pagerank'] = {
        'err': float(np.max(np.abs(p_ - ref.pagerank_ref(g)))
                     / ref.pagerank_ref(g).max()),
        'drops': st.total_drops, 'rounds': st.rounds}
    w_, st = dcra_wcc(g, mesh)
    res[f'{tag}/wcc'] = {
        'err': float(np.max(np.abs(w_ - ref.wcc_ref(g)))),
        'drops': st.total_drops, 'rounds': st.rounds}
    k_, st = dcra_kcore(g, 12, mesh)
    res[f'{tag}/kcore'] = {
        'err': float(np.max(np.abs(k_ - ref.kcore_ref(g, 12)))),
        'drops': st.total_drops, 'rounds': st.rounds}

res = {}
mesh8 = make_mesh((8,), ('data',))
mesh4 = make_mesh((4,), ('data',))
er = datasets.erdos_renyi(256, avg_degree=8, seed=5)
pl = datasets.wiki_like(512, avg_degree=8, seed=7)
run_six(er, mesh8, 'er8', res)
run_six(pl, mesh8, 'pl8', res)
run_six(er, mesh4, 'er4', res)

# disconnected graph: BFS from component A, WCC labels
dg = datasets.disconnected_pair(128, avg_degree=6, seed=11)
d_, _ = dcra_bfs(dg, 0, mesh8)
want = ref.bfs_ref(dg, 0)
res['disc/bfs'] = {'err': float(np.max(np.abs(d_ - want))),
                   'unreachable_ok': bool((d_[128:] == -1).all()
                                          and (want[128:] == -1).all()),
                   'drops': 0, 'rounds': 0}
w_, _ = dcra_wcc(dg, mesh8)
wref = ref.wcc_ref(dg)
res['disc/wcc'] = {'err': float(np.max(np.abs(w_ - wref))),
                   'two_components': bool(
                       len(np.unique(wref)) >= 2 and
                       set(np.unique(w_)) == set(np.unique(wref))),
                   'drops': 0, 'rounds': 0}
print('RESULT ' + json.dumps(res))
"""

CASES = [f"{tag}/{app}" for tag in ("er8", "pl8", "er4")
         for app in ("spmv", "histogram", "bfs", "sssp", "pagerank", "wcc",
                     "kcore")]


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("case", CASES)
def test_app_matches_oracle(results, case):
    r = results[case]
    assert r["err"] < 1e-4, r
    assert r["drops"] == 0, r


@pytest.mark.parametrize("case", [c for c in CASES if "/bfs" in c
                                  or "/sssp" in c or "/wcc" in c])
def test_iterative_apps_report_rounds_and_converge(results, case):
    assert 0 < results[case]["rounds"] < 128


def test_bfs_disconnected_unreachable_is_minus_one(results):
    r = results["disc/bfs"]
    assert r["err"] == 0 and r["unreachable_ok"]


def test_wcc_disconnected_keeps_two_components(results):
    r = results["disc/wcc"]
    assert r["err"] == 0 and r["two_components"]
