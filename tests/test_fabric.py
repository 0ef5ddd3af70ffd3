"""The Fabric layer — one topology object from single-process to
multi-host ``jax.distributed``.

Part A — in-process (1 device): ``fabric_key`` byte-compatibility with
the legacy ``_mesh_key`` tuple, the ``as_fabric`` warn-once mesh shim,
constructors + portal detection, the shared ``resolve_caps`` capacity
resolver, ``host_slice`` partition properties, chunked-ingest parity
(the global edge multiset is independent of the host count), the
``reshard`` no-op fast path (no ``device_get`` on unchanged leaves) and
``rescale`` onto a fabric's mesh, and the MeshInfo/_axsize delegation.

Part B — subprocess (8 fake host devices): for 1/2/4/8 devices a raw
Mesh launch and a ``Fabric`` launch of the same topology produce
bit-identical results/drop streams AND share ONE compile-cache entry
(hits increment, misses don't); same for the pod/portal 2x4 fabric and
``dcra_scatter``; ``ProgramServer(Fabric)`` serves identically to
``ProgramServer(mesh)``; ``Fabric.resize`` + ``rescale`` move state onto
a shrunk device set with values preserved and no-op leaves untouched.

Part C — one TRUE multi-process run: two CPU processes under
``jax.distributed`` build one Fabric (flat, and with the portal axis
across processes), run BFS, and the results and per-round message/drop
streams are bit-identical to the single-process run on the same total
device count.
"""
import json
import os
import socket
import subprocess
import sys
import warnings

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# Part A: in-process (1 device)
# ---------------------------------------------------------------------------

def _mesh1():
    from repro.core.compat import make_mesh
    return make_mesh((1,), ("data",))


def test_fabric_key_matches_legacy_mesh_key():
    from repro.core.fabric import Fabric
    mesh = _mesh1()
    legacy = (tuple(mesh.axis_names), tuple(mesh.devices.shape),
              tuple(d.id for d in mesh.devices.flat))
    f = Fabric.of(mesh)
    assert f.fabric_key() == legacy
    assert Fabric.fake(1).fabric_key() == legacy


def test_as_fabric_warns_once_and_fabric_never():
    from repro.core import fabric as fab_mod
    from repro.core.fabric import Fabric, as_fabric
    mesh = _mesh1()
    fab_mod._WARNED[0] = False
    with pytest.warns(DeprecationWarning, match="raw Mesh"):
        f1 = as_fabric(mesh)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        f2 = as_fabric(mesh)          # latched: once per process
        f3 = as_fabric(Fabric.of(mesh))
    assert not [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert f1.fabric_key() == f2.fabric_key() == f3.fabric_key()
    fab = Fabric.of(mesh)
    assert as_fabric(fab) is fab      # pass-through identity


class _DuckMesh:
    """The admission-only serve-test idiom: no axis_names, no real
    devices — Fabric accessors must stay lazy and degrade gracefully."""
    devices = np.zeros(4)


def test_of_accepts_duck_meshes_lazily():
    from repro.core.fabric import Fabric
    f = Fabric.of(_DuckMesh())
    assert f.n_devices == 4
    assert f.axis_names == ()
    assert f.process_indices == (0,) and not f.is_multiprocess


def test_portal_detection_and_pod_axis():
    from types import SimpleNamespace
    from repro.core.fabric import Fabric
    multi = SimpleNamespace(axis_names=("pod", "data"),
                            devices=np.zeros((2, 4)))
    f = Fabric.of(multi)
    assert f.portal_axis == "pod" and f.pod_axis == "pod"
    assert f.axis_sizes == {"pod": 2, "data": 4}
    assert f.axis_size(("pod", "data")) == 8 and f.axis_size(None) == 1
    # a size-1 portal axis cannot route across pods
    single = SimpleNamespace(axis_names=("pod", "data"),
                             devices=np.zeros((1, 4)))
    assert Fabric.of(single).pod_axis is None
    flat = SimpleNamespace(axis_names=("data",), devices=np.zeros(4))
    assert Fabric.of(flat).portal_axis is None


def test_launchconfig_pod_axis_for_accepts_fabric_and_mesh():
    from types import SimpleNamespace
    from repro.core.fabric import Fabric
    from repro.dse.autoconfig import LaunchConfig
    from repro.dse.space import ConfigSpace
    pt_hier = next(p for p in ConfigSpace.quick().points()
                   if p.topology == "hier_torus")
    lc = LaunchConfig(point=pt_hier, source="explicit")
    multi = SimpleNamespace(axis_names=("pod", "data"),
                            devices=np.zeros((2, 4)))
    assert lc.pod_axis_for(multi) == "pod"
    assert lc.pod_axis_for(Fabric.of(multi)) == "pod"
    flat = SimpleNamespace(axis_names=("data",), devices=np.zeros(4))
    assert lc.pod_axis_for(flat) is None


def test_resolve_caps_matches_legacy_resolvers():
    from types import SimpleNamespace
    from repro.core.queues import QueueConfig
    from repro.core.routing import (resolve_caps, resolve_flat_cap,
                                    resolve_hier_caps)
    fab = SimpleNamespace(axis_sizes={"pod": 2, "data": 4}, n_devices=8)
    q = QueueConfig.from_factor(2.0, "T3")
    caps, pods = resolve_caps(fab, q, "T3", 64, "data", None)
    assert pods is None
    assert caps == (resolve_flat_cap(q, "T3", 64, 8),)
    capsc, _ = resolve_caps(fab, q, "T3", 64, "data", None, clamp=True)
    assert capsc == (resolve_flat_cap(q, "T3", 64, 8, clamp=True),)
    caps2, pods2 = resolve_caps(fab, q, "T3", 64, "data", "pod")
    assert pods2 == (4, 2)
    assert caps2 == resolve_hier_caps(q, "T3", 64, 4, 2)
    with pytest.raises(ValueError, match="flat path"):
        resolve_caps(fab, QueueConfig.from_cap(5, "T3"), "T3", 64,
                     "data", "pod")


def test_host_slice_partitions_exactly():
    from repro.core.fabric import Fabric
    f = Fabric.of(_DuckMesh())
    for total in (0, 1, 7, 16, 23):
        for world in (1, 2, 3, 5):
            slices = [f.host_slice(total, rank=r, world=world)
                      for r in range(world)]
            # contiguous, disjoint, covering, balanced
            assert slices[0][0] == 0 and slices[-1][1] == total
            for (a, b), (c, d) in zip(slices, slices[1:]):
                assert b == c and a <= b
            lens = [hi - lo for lo, hi in slices]
            assert max(lens) - min(lens) <= 1
    with pytest.raises(ValueError, match="rank"):
        f.host_slice(8, rank=3, world=3)


def _edge_multiset(src, dst, w):
    return sorted(zip(src.tolist(), dst.tolist(), w.tolist()))


def test_ingest_union_is_host_count_independent():
    from repro.sparse.datasets import ingest_edges
    full = ingest_edges(6, edge_factor=4, seed=3, n_chunks=8)
    want = _edge_multiset(*full)
    assert len(want) > 0
    for world in (2, 3, 8):
        parts = [ingest_edges(6, edge_factor=4, seed=3, n_chunks=8,
                              rank=r, world=world) for r in range(world)]
        src = np.concatenate([p[0] for p in parts])
        dst = np.concatenate([p[1] for p in parts])
        w = np.concatenate([p[2] for p in parts])
        assert _edge_multiset(src, dst, w) == want
        # no host holds the full edge list (world > 1)
        assert all(len(p[0]) < len(full[0]) for p in parts)


def test_ingest_is_deterministic_and_fabric_driven():
    from repro.core.fabric import Fabric
    from repro.sparse.datasets import ingest_edges, rmat_edge_chunk
    a = rmat_edge_chunk(6, 2, 8, edge_factor=4, seed=3)
    b = rmat_edge_chunk(6, 2, 8, edge_factor=4, seed=3)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    f = Fabric.of(_DuckMesh())          # single "process" -> whole range
    via_fab = ingest_edges(6, edge_factor=4, seed=3, n_chunks=8, fabric=f)
    plain = ingest_edges(6, edge_factor=4, seed=3, n_chunks=8)
    assert _edge_multiset(*via_fab) == _edge_multiset(*plain)


def test_ingest_graph_runs_bfs():
    from repro.sparse.datasets import ingest_graph
    from repro.sparse.jax_apps import dcra_bfs
    from repro.sparse.options import LaunchOptions
    from repro.core.fabric import Fabric
    g = ingest_graph(6, edge_factor=4, seed=3, n_chunks=4)
    d, stats = dcra_bfs(g, 0, Fabric.fake(1),
                        options=LaunchOptions(capacity_factor=8.0))
    assert d.shape == (64,) and stats.rounds > 0


def test_reshard_skips_noop_leaves(monkeypatch):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.runtime import elastic
    mesh = _mesh1()
    sh = NamedSharding(mesh, P("data"))
    x = jax.device_put(np.arange(8, dtype=np.float32), sh)
    calls = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda v: (calls.append(1), real_get(v))[1])
    out = elastic.reshard({"a": x}, {"a": sh})
    assert calls == []                  # unchanged path: no host round-trip
    assert out["a"] is x
    sh2 = NamedSharding(mesh, P())
    out2 = elastic.reshard({"a": x}, {"a": sh2})
    assert len(calls) == 1              # a real move still round-trips
    assert out2["a"].sharding == sh2
    assert np.array_equal(np.asarray(out2["a"]), np.arange(8))


def test_rescale_places_leaves_on_fabric_mesh():
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax.numpy as jnp
    from repro.core.fabric import Fabric
    from repro.runtime.elastic import rescale
    fab = Fabric.fake(1)
    tree = {"w": jnp.arange(8.0), "b": jnp.arange(4.0)}
    out = rescale(tree, fab, {"w": P("data"), "b": P()})
    assert out["w"].sharding == NamedSharding(fab.mesh, P("data"))
    assert out["b"].sharding == NamedSharding(fab.mesh, P())
    assert np.array_equal(np.asarray(out["w"]), np.arange(8.0))


def test_meshinfo_and_axsize_delegate_to_fabric():
    from repro.core.dispatch import MeshInfo
    from repro.core.fabric import Fabric
    from repro.launch.sharding import _axsize
    mesh = _mesh1()
    mi = MeshInfo(mesh)
    assert mi.axis_size(None) == 1
    assert mi.axis_size("data") == 1
    assert mi.axis_size(["data"]) == 1 and mi.axis_size(("data",)) == 1
    fab = Fabric.of(mesh)
    assert MeshInfo(fab).mesh is mesh   # Fabric accepted, unwrapped
    assert _axsize(mesh, ("data",)) == 1 and _axsize(fab, None) == 1


def test_launch_mesh_fabric_constructors_share_shapes():
    # shape/axis contracts only — 256-device meshes can't build here
    from repro.launch import mesh as lm
    assert lm.make_production_fabric.__doc__ is not None
    from repro.core.fabric import Fabric
    from types import SimpleNamespace
    pod = SimpleNamespace(axis_names=("pod", "data", "model"),
                          devices=np.zeros((2, 16, 16)))
    assert lm.model_axes(pod) == ("model",)
    assert lm.batch_axes(pod) == ("pod", "data")
    assert lm.batch_axes(Fabric.of(pod)) == ("pod", "data")


# ---------------------------------------------------------------------------
# Part B: subprocess, 8 fake host devices — Fabric vs raw-Mesh parity
# ---------------------------------------------------------------------------

SCRIPT_B = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.compat import make_mesh
from repro.core.fabric import Fabric
from repro.sparse import datasets, program
from repro.sparse.jax_apps import dcra_bfs, dcra_scatter
from repro.sparse.options import LaunchOptions
from repro.serve.engine import ProgramServer, Request

res = {}
g = datasets.wiki_like(192, avg_degree=6, seed=7)

# -- flat parity + shared cache entry at every device count -----------------
for n_dev in (1, 2, 4, 8):
    mesh = make_mesh((n_dev,), ('data',))
    d1, s1 = dcra_bfs(g, 0, mesh,                    # overflowing
                      options=LaunchOptions(capacity_factor=0.25))
    c0 = program.cache_stats()
    d2, s2 = dcra_bfs(g, 0, Fabric.fake(n_dev),
                      options=LaunchOptions(capacity_factor=0.25))
    c1 = program.cache_stats()
    res[f'flat{n_dev}'] = {
        'equal': bool(np.array_equal(d1, d2)),
        'msgs_equal': bool(np.array_equal(s1.messages, s2.messages)),
        'drops_equal': bool(np.array_equal(s1.drops, s2.drops)),
        'drops_total': int(s1.total_drops),
        'hit_delta': c1['hits'] - c0['hits'],
        'miss_delta': c1['misses'] - c0['misses']}

# -- pod/portal parity ------------------------------------------------------
hier_mesh = make_mesh((2, 4), ('pod', 'data'))
hier_fab = Fabric.single((2, 4), ('pod', 'data'))
d1, s1 = dcra_bfs(g, 0, hier_mesh,
                  options=LaunchOptions(pod_axis='pod', capacity_factor=0.25))
c0 = program.cache_stats()
d2, s2 = dcra_bfs(g, 0, hier_fab,
                  options=LaunchOptions(pod_axis='pod', capacity_factor=0.25))
c1 = program.cache_stats()
res['hier'] = {
    'equal': bool(np.array_equal(d1, d2)),
    'msgs_equal': bool(np.array_equal(s1.messages, s2.messages)),
    'drops_equal': bool(np.array_equal(s1.drops, s2.drops)),
    'portal': hier_fab.pod_axis,
    'hit_delta': c1['hits'] - c0['hits'],
    'miss_delta': c1['misses'] - c0['misses']}

# -- one-round scatter parity ----------------------------------------------
dest = jnp.asarray(np.arange(64) % 16)
vals = jnp.ones(64, jnp.float32)
mesh8 = make_mesh((8,), ('data',))
y1, dr1 = dcra_scatter(dest, vals, 16, mesh8,
                       options=LaunchOptions(capacity_factor=2.0))
c0 = program.cache_stats()
y2, dr2 = dcra_scatter(dest, vals, 16, Fabric.fake(8),
                       options=LaunchOptions(capacity_factor=2.0))
c1 = program.cache_stats()
res['scatter'] = {'equal': bool(np.array_equal(np.asarray(y1),
                                               np.asarray(y2))),
                  'drops_equal': int(dr1) == int(dr2),
                  'hit_delta': c1['hits'] - c0['hits'],
                  'miss_delta': c1['misses'] - c0['misses']}

# -- ProgramServer(Fabric) vs ProgramServer(mesh) ---------------------------
reqs = [Request(req_id=i, tenant=f't{i % 3}', program='bfs', graph='g',
                root=(7 * i) % g.n) for i in range(6)]
srv_mesh = ProgramServer(make_mesh((4,), ('data',)), {'g': g},
                         batch_width=2)
srv_fab = ProgramServer(Fabric.fake(4), {'g': g}, batch_width=2)
r1 = srv_mesh.run(list(reqs))
r2 = srv_fab.run(list(reqs))
res['serve'] = {
    'statuses': [a.status for a in r1] == [b.status for b in r2],
    'results': all((a.result is None and b.result is None)
                   or bool(np.array_equal(a.result, b.result))
                   for a, b in zip(r1, r2)),
    'n': len(r1) == len(reqs) == len(r2)}

# -- elastic: resize + rescale ---------------------------------------------
fab8 = Fabric.fake(8)
fab4 = fab8.resize(jax.devices()[:4])
from repro.runtime.elastic import rescale
x = jax.device_put(np.arange(16, dtype=np.float32),
                   NamedSharding(fab8.mesh, P('data')))
moved = rescale({'x': x}, fab4, {'x': P('data')})
same = rescale(moved, fab4, {'x': P('data')})        # no-op second pass
hier_small = Fabric.single((2, 4), ('pod', 'data')).resize(jax.devices()[:4])
res['elastic'] = {
    'shape4': fab4.shape == (4,),
    'names': fab4.axis_names == ('data',),
    'values': bool(np.array_equal(np.asarray(moved['x']), np.arange(16))),
    'moved_sharding': moved['x'].sharding == NamedSharding(fab4.mesh,
                                                           P('data')),
    'noop_identity': same['x'] is moved['x'],
    'hier_shape': hier_small.shape == (1, 4),
    'hier_names': hier_small.axis_names == ('pod', 'data'),
    'hier_pod_off': hier_small.pod_axis is None,
    'key_stable': fab8.fabric_key() == Fabric.fake(8).fabric_key()}
print('RESULT ' + json.dumps(res))
"""


@pytest.fixture(scope="module")
def results_b():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", SCRIPT_B], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_fabric_and_mesh_launches_are_bit_identical(results_b, n_dev):
    r = results_b[f"flat{n_dev}"]
    assert r["equal"] and r["msgs_equal"] and r["drops_equal"], r
    # the Fabric launch HIT the raw-mesh launch's cache entry: same key
    assert r["hit_delta"] >= 1 and r["miss_delta"] == 0, r


def test_some_flat_case_exercises_drops(results_b):
    assert any(results_b[f"flat{n}"]["drops_total"] > 0
               for n in (2, 4, 8)), "capacity_factor=0.25 should drop"


def test_pod_portal_fabric_parity(results_b):
    r = results_b["hier"]
    assert r["equal"] and r["msgs_equal"] and r["drops_equal"], r
    assert r["portal"] == "pod"
    assert r["hit_delta"] >= 1 and r["miss_delta"] == 0, r


def test_scatter_fabric_parity(results_b):
    r = results_b["scatter"]
    assert r["equal"] and r["drops_equal"], r
    assert r["hit_delta"] >= 1 and r["miss_delta"] == 0, r


def test_program_server_accepts_fabric(results_b):
    r = results_b["serve"]
    assert r["statuses"] and r["results"] and r["n"], r


def test_elastic_resize_and_rescale(results_b):
    r = results_b["elastic"]
    assert all(r.values()), r


# ---------------------------------------------------------------------------
# Part C: TRUE multi-process (2 CPU processes over jax.distributed)
# ---------------------------------------------------------------------------

WORKER_C = r"""
import os, sys
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'
os.environ.setdefault('JAX_PLATFORMS', 'cpu')
import json
import numpy as np

coord, pid = sys.argv[1], int(sys.argv[2])
try:
    from repro.core.fabric import Fabric
    fab = Fabric.distributed(coordinator_address=coord, num_processes=2,
                             process_id=pid)
except Exception as e:     # no multi-process runtime in this env
    print('UNSUPPORTED ' + repr(e), flush=True)
    sys.exit(17)

import jax
assert fab.is_multiprocess and fab.n_processes == 2, fab.process_indices
assert fab.n_devices == 4 and fab.axis_names == ('data',)
assert fab.dcn_axes() == ('data',)     # flat: every hop crosses the DCN
assert fab.host_slice(8) in ((0, 4), (4, 8))

from repro.sparse import datasets
from repro.sparse.jax_apps import dcra_bfs
from repro.sparse.options import LaunchOptions

g = datasets.erdos_renyi(96, avg_degree=6, seed=5)
res = {}
d, st = dcra_bfs(g, 0, fab, options=LaunchOptions(capacity_factor=1.0))
res['flat'] = {'dist': np.asarray(d).tolist(),
               'messages': st.messages.tolist(),
               'drops': st.drops.tolist(), 'rounds': st.rounds}

# portal axis ACROSS the two processes (leading axis is process-major)
hier = Fabric.distributed((2, 2), ('portal', 'data'), portal_axis='portal')
assert hier.dcn_axes() == ('portal',)  # only the portal hop crosses DCN
assert hier.pod_axis == 'portal'
d2, st2 = dcra_bfs(g, 0, hier, options=LaunchOptions(pod_axis='portal',
                                                     capacity_factor=1.0))
res['hier'] = {'dist': np.asarray(d2).tolist(),
               'messages': st2.messages.tolist(),
               'drops': st2.drops.tolist(), 'rounds': st2.rounds}

if pid == 0:
    print('RESULT ' + json.dumps(res), flush=True)
"""

REF_C = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import json
import numpy as np
from repro.core.fabric import Fabric
from repro.sparse import datasets
from repro.sparse.jax_apps import dcra_bfs
from repro.sparse.options import LaunchOptions

g = datasets.erdos_renyi(96, avg_degree=6, seed=5)
res = {}
d, st = dcra_bfs(g, 0, Fabric.fake(4),
                 options=LaunchOptions(capacity_factor=1.0))
res['flat'] = {'dist': np.asarray(d).tolist(),
               'messages': st.messages.tolist(),
               'drops': st.drops.tolist(), 'rounds': st.rounds}
hier = Fabric.single((2, 2), ('portal', 'data'), portal_axis='portal')
d2, st2 = dcra_bfs(g, 0, hier, options=LaunchOptions(pod_axis='portal',
                                                     capacity_factor=1.0))
res['hier'] = {'dist': np.asarray(d2).tolist(),
               'messages': st2.messages.tolist(),
               'drops': st2.drops.tolist(), 'rounds': st2.rounds}
print('RESULT ' + json.dumps(res), flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _result_line(stdout):
    lines = [l for l in stdout.splitlines() if l.startswith("RESULT ")]
    assert lines, stdout[-2000:]
    return json.loads(lines[0][len("RESULT "):])


def test_two_process_fabric_matches_single_process():
    """The acceptance-criteria run: 2 real CPU processes, one Fabric,
    portal axis across the DCN — BFS dist + per-round message/drop
    streams bit-identical to single-process on 4 total devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    coord = f"127.0.0.1:{_free_port()}"
    procs = [subprocess.Popen(
        [sys.executable, "-c", WORKER_C, coord, str(pid)], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=600))
    finally:
        for p in procs:
            p.kill()
    if any(p.returncode == 17 for p in procs):
        pytest.skip("jax.distributed multi-process unavailable: "
                    + (outs[0][0] + outs[1][0])[:500])
    for p, (so, se) in zip(procs, outs):
        assert p.returncode == 0, (so[-1500:], se[-3000:])
    dist_res = _result_line(outs[0][0])

    ref = subprocess.run([sys.executable, "-c", REF_C], env=env,
                         capture_output=True, text=True, timeout=600)
    assert ref.returncode == 0, ref.stderr[-3000:]
    ref_res = _result_line(ref.stdout)

    for k in ("flat", "hier"):
        assert dist_res[k] == ref_res[k], (k, dist_res[k], ref_res[k])
