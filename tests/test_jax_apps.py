"""Executable JAX sparse apps: single-device jnp versions vs numpy oracles,
and the distributed owner-routed round on 8 fake devices (subprocess)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sparse import datasets, ref
from repro.sparse.jax_apps import bfs_jnp, histogram_jnp, spmv_jnp


@pytest.fixture(scope="module")
def graph():
    return datasets.rmat(9, edge_factor=8, seed=3)


def test_spmv_jnp(graph):
    x = np.random.default_rng(0).random(graph.n)
    y = spmv_jnp(jnp.asarray(graph.row_of()), jnp.asarray(graph.col_idx),
                 jnp.asarray(graph.values), jnp.asarray(x), graph.n)
    assert np.allclose(np.asarray(y), ref.spmv_ref(graph, x), rtol=1e-5,
                       atol=1e-3)


def test_bfs_jnp(graph):
    d = bfs_jnp(jnp.asarray(graph.row_of()), jnp.asarray(graph.col_idx),
                graph.n, 0, max_levels=64)
    want = ref.bfs_ref(graph, 0).astype(float)
    got = np.where(np.isinf(np.asarray(d)), -1, np.asarray(d))
    assert np.array_equal(got, want)


def test_histogram_jnp():
    els = datasets.histogram_data(1 << 12, 128)
    h = histogram_jnp(jnp.asarray(els), 128)
    assert np.array_equal(np.asarray(h), ref.histogram_ref(els, 128))


SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import jax, numpy as np
from repro.core.compat import make_mesh, set_mesh
from repro.sparse import datasets, ref
from repro.sparse.jax_apps import dcra_histogram, dcra_spmv
from repro.sparse.options import LaunchOptions

mesh = make_mesh((8,), ('data',))
g = datasets.rmat(9, edge_factor=8, seed=3)
x = np.random.default_rng(0).random(g.n)
res = {}
with set_mesh(mesh):
    y, dropped = dcra_spmv(g, x, mesh)
    res['spmv_err'] = float(np.max(np.abs(np.asarray(y) - ref.spmv_ref(g, x))))
    res['spmv_dropped'] = int(dropped)
    els = datasets.histogram_data(1 << 12, 128)
    h, d2 = dcra_histogram(els, 128, mesh)
    res['hist_exact'] = bool(
        np.array_equal(np.asarray(h), ref.histogram_ref(els, 128)))
    res['hist_dropped'] = int(d2)
    # tight queues DO drop (the paper's overflow semantics)
    _, d3 = dcra_histogram(els, 128, mesh,
                           options=LaunchOptions(capacity_factor=0.2))
    res['tight_queue_drops'] = int(d3)
print('RESULT ' + json.dumps(res))
"""


@pytest.fixture(scope="module")
def dist():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_distributed_spmv_exact(dist):
    assert dist["spmv_dropped"] == 0
    assert dist["spmv_err"] < 1e-2


def test_distributed_histogram_exact(dist):
    assert dist["hist_exact"] and dist["hist_dropped"] == 0


def test_queue_overflow_drops_when_undersized(dist):
    assert dist["tight_queue_drops"] > 0
