"""The pipelined round shape and the LaunchOptions launch surface.

Part A — in-process (1 device): ``LaunchOptions`` is the one launch
surface (its conflicts raise, the old per-setting keywords are gone),
``round_mode`` lands in the compile-cache key, every entrypoint accepts
``options=``, ``local_route_reduce`` is bit-identical to the two-pass
``bucket`` + ``reduce_received`` shape, and a pipelined
``ProgramServer`` serves identically. On a
one-device fabric both round modes run the local fold: the seven apps
agree bitwise across modes and match the oracles, tight caps drop as the
analytic twin says, and ``cache_stats()["local_fold_builds"]`` counts the
folded builds.

Part B (subprocess, 8 fake host devices) — the bit-identity contract of
``round_mode="pipelined"``: for every iterative program, flat AND
pod/portal, loose AND overflowing caps, 1/2/4/8 devices, the pipelined
executable's results, rounds, and per-round message/drop streams equal
lockstep's exactly — and the UNCHANGED analytic twin
(``program_app_stats``) still matches the pipelined run.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ITER_APPS = ("bfs", "sssp", "wcc", "pagerank", "kcore")


# ---------------------------------------------------------------------------
# Part A: the launch surface (1 device, in-process)
# ---------------------------------------------------------------------------

def _tiny():
    from repro.sparse import datasets
    return datasets.wiki_like(96, avg_degree=4, seed=11)


def _mesh1():
    from repro.core.compat import make_mesh
    return make_mesh((1,), ("data",))


def test_round_mode_is_a_cache_key_dimension():
    from repro.sparse import LaunchOptions, program
    from repro.sparse.jax_apps import dcra_bfs
    g, mesh = _tiny(), _mesh1()
    program.clear_cache()
    dcra_bfs(g, 0, mesh)
    assert program.cache_stats()["misses"] == 1
    dcra_bfs(g, 0, mesh, options=LaunchOptions(round_mode="pipelined"))
    assert program.cache_stats()["misses"] == 2
    dcra_bfs(g, 0, mesh, options=LaunchOptions(round_mode="pipelined"))
    assert program.cache_stats()["misses"] == 2    # pipelined entry reused
    dcra_bfs(g, 0, mesh, options=LaunchOptions(round_mode="lockstep"))
    assert program.cache_stats()["misses"] == 2    # lockstep is the default


def test_option_conflicts_raise():
    """``LaunchOptions.resolve`` is the one conflict check, and the
    per-setting keywords of the old launch surface are gone."""
    from repro.sparse import LaunchOptions
    from repro.sparse.jax_apps import dcra_bfs, dcra_spmv
    g = _tiny()
    with pytest.raises(ValueError, match="conflicts"):
        dcra_bfs(g, 0, mesh=None,
                 options=LaunchOptions(cap=4, capacity_factor=2.0))
    with pytest.raises(ValueError, match="conflicts"):
        dcra_spmv(g, np.ones(g.n), mesh=None,
                  options=LaunchOptions(cap=4, config="auto"))
    with pytest.raises(ValueError, match="round_mode"):
        dcra_bfs(g, 0, mesh=None, options=LaunchOptions(round_mode="warp"))
    with pytest.raises(TypeError):
        dcra_bfs(g, 0, mesh=None, cap=4)


def test_every_entrypoint_accepts_options():
    """All seven dcra_* apps + run_program + dcra_scatter take options=
    and agree bitwise with the default launch (one device drops nothing
    at either capacity factor)."""
    from repro.sparse import LaunchOptions, jax_apps
    from repro.sparse import datasets
    from repro.sparse.jax_apps import PROGRAMS, dcra_scatter, run_program
    import jax.numpy as jnp
    g, mesh = _tiny(), _mesh1()
    x = np.random.default_rng(0).random(g.n)
    els = datasets.histogram_data(512, 16, seed=4)
    opts = LaunchOptions(capacity_factor=2.0)
    calls = {
        "bfs": lambda **kw: jax_apps.dcra_bfs(g, 0, mesh, **kw),
        "sssp": lambda **kw: jax_apps.dcra_sssp(g, 0, mesh, **kw),
        "wcc": lambda **kw: jax_apps.dcra_wcc(g, mesh, **kw),
        "pagerank": lambda **kw: jax_apps.dcra_pagerank(
            g, mesh, iters=3, **kw),
        "kcore": lambda **kw: jax_apps.dcra_kcore(g, 3, mesh, **kw),
        "spmv": lambda **kw: jax_apps.dcra_spmv(g, x, mesh, **kw),
        "histogram": lambda **kw: jax_apps.dcra_histogram(
            els, 16, mesh, **kw),
    }
    assert set(calls) == set(PROGRAMS)
    for app, call in calls.items():
        got, _ = call(options=opts)
        want, _ = call()
        assert np.array_equal(np.asarray(got), np.asarray(want)), app
    r1, s1 = run_program(PROGRAMS["bfs"], g, mesh, options=opts,
                         params={"root": 0})
    r2, _ = run_program(PROGRAMS["bfs"], g, mesh, params={"root": 0})
    assert np.array_equal(np.asarray(r1), np.asarray(r2))
    assert s1.total_drops == 0
    dest = jnp.asarray(np.arange(32) % 8)
    vals = jnp.ones(32, jnp.float32)
    y1, d1 = dcra_scatter(dest, vals, 8, mesh, options=opts)
    y2, _ = dcra_scatter(dest, vals, 8, mesh)
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    assert int(d1) == 0


@pytest.mark.parametrize("op,s", [("min", 8), ("store", 8), ("min", 1),
                                  ("add", 1)])
def test_local_route_reduce_matches_two_pass_shape(op, s):
    """The local fold == bucket + reduce_received, bitwise, including the
    drop count, under overflowing caps: min / store with any number of
    buckets, add with one (array order is bucket order only there)."""
    import jax.numpy as jnp
    from repro.core.routing import (bucket, local_route_reduce,
                                    reduce_received)
    rng = np.random.default_rng(5)
    n, n_local = 512, 64
    cap = 16 if s > 1 else 100                 # 512 >> s*cap: drops
    dest = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.8)
    vals = jnp.asarray(rng.standard_normal(n), jnp.float32)
    slots = jnp.asarray(rng.integers(0, n_local, n), jnp.int32)
    xb, (slot_b,), _, nd_ref = bucket(vals[:, None], dest, valid, [slots],
                                      s, cap)
    want = reduce_received(slot_b, xb[:, 0], n_local, op)
    got, nd = local_route_reduce(vals, slots, dest, valid, s, cap,
                                 n_local, op)
    assert int(nd) == int(nd_ref) and int(nd) > 0
    assert np.array_equal(np.asarray(want).view(np.uint32),
                          np.asarray(got).view(np.uint32))


def test_local_route_reduce_refuses_add_over_several_buckets():
    import jax.numpy as jnp
    from repro.core.routing import local_route_reduce
    dest = jnp.asarray(np.arange(32) % 4, jnp.int32)
    ones = jnp.ones(32, jnp.float32)
    with pytest.raises(ValueError, match="one bucket"):
        local_route_reduce(ones, dest, dest, ones > 0, 4, 8, 4, "add")


def test_pipelined_program_server_serves_identically():
    from repro.serve import LaunchOptions, ProgramServer, Request
    g, mesh = _tiny(), _mesh1()
    reqs = [Request(req_id=i, tenant=f"t{i % 2}", program=p, graph="g",
                    root=i % g.n)
            for i, p in enumerate(("bfs", "sssp", "bfs", "sssp"))]
    base = ProgramServer(mesh, {"g": g}).run(list(reqs))
    pipe = ProgramServer(
        mesh, {"g": g},
        options=LaunchOptions(round_mode="pipelined")).run(list(reqs))
    assert len(base) == len(pipe) == len(reqs)
    for a, b in zip(base, pipe):
        assert a.status == b.status and a.rounds == b.rounds
        assert np.array_equal(np.asarray(a.result), np.asarray(b.result))
    with pytest.raises(TypeError):
        ProgramServer(mesh, {"g": g}, axis="model",
                      options=LaunchOptions())


# ---------------------------------------------------------------------------
# Part A, one device: both round modes run the local fold
# ---------------------------------------------------------------------------

def _fabric1():
    from repro.core.fabric import Fabric
    return Fabric.single((1,), ("data",))


def _graph256():
    from repro.sparse import datasets
    return datasets.wiki_like(256, avg_degree=8, seed=7)


ONE_DEVICE_PARAMS = {"bfs": {"root": 0}, "sssp": {"root": 0}, "wcc": {},
                     "pagerank": {"damping": 0.85, "iters": 20},
                     "kcore": {"k": 8.0}, "spmv": {}, "histogram": {}}


def _one_device_run(app, round_mode, **kw):
    """``run_program`` of ``app`` on a one-device fabric and its oracle's
    answer, each in the app's own units (hops, labels, degrees)."""
    from repro.sparse import LaunchOptions, datasets, ref
    from repro.sparse.jax_apps import PROGRAMS, run_program
    g = _graph256()
    x = np.random.default_rng(0).random(g.n)
    els = datasets.histogram_data(1 << 11, 64, seed=4)
    data = {"spmv": (g, x), "histogram": (els, 64)}.get(app, g)
    out, stats = run_program(
        PROGRAMS[app], data, _fabric1(), params=ONE_DEVICE_PARAMS[app],
        options=LaunchOptions(round_mode=round_mode, **kw))
    if app in ("spmv", "histogram"):
        got = np.asarray(out)
        want = (ref.spmv_ref(g, x) if app == "spmv"
                else ref.histogram_ref(els, 64))
    elif app in ("bfs", "sssp"):
        got = out[0]
        want = (ref.bfs_ref(g, 0) if app == "bfs" else ref.sssp_ref(g, 0))
        if app == "bfs":
            got = np.where(np.isfinite(got), got, -1)
    elif app == "kcore":
        deg, alive = out
        got, want = np.where(alive > 0, deg, -1), ref.kcore_ref(g, 8)
    else:
        got = out[0]
        want = ref.wcc_ref(g) if app == "wcc" else ref.pagerank_ref(g)
    return np.asarray(got, np.float64), np.asarray(want, np.float64), stats


@pytest.mark.parametrize("app", sorted(ONE_DEVICE_PARAMS))
def test_one_device_modes_agree_and_match_oracle(app):
    """Lockstep and pipelined run the same folded round on one device:
    bitwise the same answer and stats, the oracle's answer, no drops."""
    got_l, want, s_l = _one_device_run(app, "lockstep")
    got_p, _, s_p = _one_device_run(app, "pipelined")
    assert np.array_equal(got_l.view(np.uint64), got_p.view(np.uint64))
    assert s_l.rounds == s_p.rounds
    assert np.array_equal(s_l.messages, s_p.messages)
    assert np.array_equal(s_l.drops, s_p.drops)
    both = np.isfinite(want)
    assert np.array_equal(np.isfinite(got_l), both)
    err = np.abs(got_l[both] - want[both]).max()
    # relative to the largest answer where the answer is a float sum
    scale = {"pagerank": want.max(),
             "spmv": max(1.0, np.abs(want).max())}.get(app, 1.0)
    assert err / scale < 1e-4, err
    assert s_l.total_drops == 0


@pytest.mark.parametrize("app", ITER_APPS)
def test_one_device_tight_cap_streams_match_twin(app):
    """Under cap=2 the fold drops tasks, and both modes' message and drop
    streams are the analytic twin's, round for round."""
    from repro.sparse.jax_apps import PROGRAMS
    from repro.sparse.program import program_app_stats
    twin = program_app_stats(PROGRAMS[app], _graph256(), 1, cap=2,
                             params=ONE_DEVICE_PARAMS[app])
    got = {}
    for mode in ("lockstep", "pipelined"):
        got[mode], _, stats = _one_device_run(app, mode, cap=2)
        assert stats.total_drops > 0
        assert stats.rounds == twin.rounds
        assert np.array_equal(stats.messages, twin.messages)
        assert np.array_equal(stats.drops, twin.drops)
    assert np.array_equal(got["lockstep"].view(np.uint64),
                          got["pipelined"].view(np.uint64))


def test_local_fold_builds_count_one_device_graph_callables():
    from repro.sparse import LaunchOptions, program
    from repro.sparse.jax_apps import dcra_bfs, dcra_pagerank
    g, fab = _graph256(), _fabric1()
    program.clear_cache()
    dcra_bfs(g, 0, fab)
    dcra_bfs(g, 1, fab)                          # same shape: a cache hit
    dcra_bfs(g, 0, fab, options=LaunchOptions(round_mode="pipelined"))
    dcra_pagerank(g, fab, iters=3)
    stats = program.cache_stats()
    assert stats["misses"] == 3 and stats["hits"] == 1
    assert stats["local_fold_builds"] == 3
    program.clear_cache()
    assert program.cache_stats()["local_fold_builds"] == 0


# ---------------------------------------------------------------------------
# Part B: pipelined == lockstep under shard_map (subprocess)
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import jax
import numpy as np
from repro.core.compat import make_mesh
from repro.sparse import LaunchOptions, datasets
from repro.sparse.jax_apps import PROGRAMS
from repro.sparse.program import program_app_stats, run_program

g = datasets.wiki_like(256, avg_degree=8, seed=7)
PARAMS = {'bfs': {'root': 0}, 'sssp': {'root': 0}, 'wcc': {},
          'pagerank': {'damping': 0.85, 'iters': 4}, 'kcore': {'k': 8.0}}
ITER = tuple(PARAMS)

def pair(app, mesh, n_dev, tag, twin_kw, **kw):
    r_l, s_l = run_program(PROGRAMS[app], g, mesh, params=PARAMS[app],
                           options=LaunchOptions(round_mode='lockstep',
                                                 **kw))
    r_p, s_p = run_program(PROGRAMS[app], g, mesh, params=PARAMS[app],
                           options=LaunchOptions(round_mode='pipelined',
                                                 **kw))
    leaves = zip(jax.tree_util.tree_leaves(r_l),
                 jax.tree_util.tree_leaves(r_p))
    twin = program_app_stats(PROGRAMS[app], g, n_dev, params=PARAMS[app],
                             **twin_kw)
    return {'app': app, 'n_dev': n_dev, 'tag': tag,
            'results_equal': all(np.array_equal(np.asarray(a),
                                                np.asarray(b))
                                 for a, b in leaves),
            'rounds_equal': s_l.rounds == s_p.rounds,
            'streams_equal': (np.array_equal(s_l.messages, s_p.messages)
                              and np.array_equal(s_l.drops, s_p.drops)),
            'twin_ok': (twin.rounds == s_p.rounds
                        and np.array_equal(twin.messages, s_p.messages)
                        and np.array_equal(twin.drops, s_p.drops)),
            'drops': int(s_p.total_drops), 'rounds': int(s_p.rounds)}

cases = []
for n_dev in (1, 2, 4, 8):
    mesh = make_mesh((n_dev,), ('data',))
    apps = ITER if n_dev in (1, 8) else ('bfs',)
    for app in apps:
        cases.append(pair(app, mesh, n_dev, 'cap2', {'cap': 2}, cap=2))
        if n_dev == 8:
            cases.append(pair(app, mesh, n_dev, 'cf4',
                              {'capacity_factor': 4.0},
                              capacity_factor=4.0))
hier = make_mesh((2, 4), ('pod', 'data'))
for app, cf in (('bfs', 0.25), ('bfs', 4.0), ('pagerank', 0.5)):
    cases.append(pair(app, hier, 8, f'pod-cf{cf}',
                      {'capacity_factor': cf, 'pods': (4, 2)},
                      pod_axis='pod', capacity_factor=cf))
print('RESULT ' + json.dumps(cases))
"""


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("app", ITER_APPS)
def test_pipelined_is_bit_identical_to_lockstep(cases, app):
    mine = [c for c in cases if c["app"] == app]
    assert mine, app
    bad = [c for c in mine if not (c["results_equal"] and c["rounds_equal"]
                                   and c["streams_equal"])]
    assert not bad, bad


@pytest.mark.parametrize("app", ITER_APPS)
def test_unchanged_twin_matches_pipelined(cases, app):
    """program_app_stats needed NO pipelined variant — the analytic twin
    models rounds, and the pipeline only reshapes their execution."""
    bad = [c for c in cases if c["app"] == app and not c["twin_ok"]]
    assert not bad, bad


def test_tight_caps_drop_under_pipelining(cases):
    """cap=2 must overflow in the pipelined shape too, or the drop-stream
    agreement above is vacuous."""
    for app in ITER_APPS:
        tight = [c for c in cases if c["app"] == app and c["tag"] == "cap2"]
        assert any(c["drops"] > 0 for c in tight), (app, tight)


def test_pod_portal_covered_both_modes(cases):
    pods = [c for c in cases if c["tag"].startswith("pod")]
    assert {c["app"] for c in pods} == {"bfs", "pagerank"}
    assert all(c["results_equal"] and c["streams_equal"] and c["twin_ok"]
               for c in pods), pods
