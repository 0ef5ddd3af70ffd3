"""The owner bound caps a flat round's buckets and changes no answer (CPU).

A packed graph knows the most edges one device's block holds for one
owner (``PackedGraph.owner_bound``). A flat round's per-channel cap is
clamped at ``round8`` of it: no bucket is ever offered more tasks than
that, so the clamp shrinks the wire and the receive buffer and admits
exactly what the larger cap admits.

* ``_owner_bound`` equals a brute-force count over the edge list.
* On 4 fake devices, flat (4,), lockstep and pipelined: BFS, SSSP, WCC
  and PageRank give bit-identical states and ``AppStats`` to the same
  launch with the bound patched back to E_max, and the twin agrees.
* ``wire_plan`` gives ``min(old cap, round8(bound))`` and the lowered
  all-to-all carries 4 buckets of that many rows.
* An explicit cap below the bound drops as many tasks as the twin and as
  the launch with the bound patched back to E_max.
* ``owner_capped_builds`` counts 1 per flat four-device shape, 0 on one
  device and on (2, 2) pods.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re
import numpy as np
from repro.core.fabric import Fabric
from repro.core.routing import resolve_caps
from repro.sparse import LaunchOptions, datasets, program
from repro.sparse.jax_apps import BFS, PAGERANK, SSSP, WCC

G = datasets.rmat(11, edge_factor=8, seed=5)
APPS = {"bfs": (BFS, {"root": 0}), "sssp": (SSSP, {"root": 0}),
        "wcc": (WCC, {}), "pagerank": (PAGERANK, {"damping": 0.85,
                                                 "iters": 5})}
FABRICS = {"flat": (Fabric.single((4,), ("data",)), None),
           "pods": (Fabric.single((2, 2), ("pod", "data")), "pod"),
           "one": (Fabric.single((1,), ("data",)), None)}
A2A = re.compile(r"= s32\[(\d+),(\d+)\]\{[^}]*\} all-to-all\(")
BOUND = program._owner_bound


def brute_bound(prog):
    rows, cols = G.row_of(), G.col_idx.astype(np.int64)
    if prog.undirected:
        rows, cols = np.r_[rows, cols], np.r_[cols, rows]
    return int(np.bincount((rows % 4) * 4 + cols % 4, minlength=16).max())


def launch(app, fabric, mode, cap=None, at_e_max=False):
    # one fresh launch: empty compile cache and pack memo, zeroed counters
    prog, params = APPS[app]
    fab, pod_axis = FABRICS[fabric]
    opts = LaunchOptions(axis="data", pod_axis=pod_axis, round_mode=mode,
                         cap=cap)
    texts, build = [], program._build_graph_fn

    def spy(*a, **kw):
        fn = build(*a, **kw)

        def call(*args):
            texts.append(fn.lower(*args).as_text(dialect="hlo"))
            return fn(*args)
        return call

    program.clear_cache()
    program._build_graph_fn = spy
    if at_e_max:
        program._owner_bound = lambda dst, n_dev: len(dst) // n_dev
    try:
        states, stats = program.run_program(prog, G, fab, params=params,
                                            options=opts)
        program.run_program(prog, G, fab, params=params, options=opts)
        plan = program.wire_plan(prog, G, fab, options=opts)
        pg = program.packed_graph(G, fab.n_devices,
                                  undirected=prog.undirected)
        old, _ = resolve_caps(fab, program._resolve_queues(
            prog, None, cap, None), prog.task, pg.E_max, "data", pod_axis,
            clamp=True)
        twin = program.program_app_stats(
            prog, G, fab.n_devices, cap=cap, params=params,
            pods=(2, 2) if pod_axis else None)
    finally:
        program._build_graph_fn = build
        program._owner_bound = BOUND
    return {"states": [s.tolist() for s in states],
            "messages": stats.messages.tolist(),
            "drops": stats.drops.tolist(),
            "twin": [twin.messages.tolist(), twin.drops.tolist()],
            "caps": list(plan.caps), "old_caps": list(old),
            "slots": list(plan.slots_per_round),
            "a2a": [[int(r), int(c)] for r, c in A2A.findall(texts[0])],
            "bound": pg.owner_bound, "brute_bound": brute_bound(prog),
            "e_max": pg.E_max,
            "owner_capped_builds":
                program.cache_stats()["owner_capped_builds"]}


res = {}
for app in APPS:
    for mode in ("lockstep", "pipelined"):
        res[f"{app}-flat-{mode}"] = launch(app, "flat", mode)
        res[f"{app}-flat-{mode}-e_max"] = launch(app, "flat", mode,
                                                 at_e_max=True)
for fabric in ("pods", "one"):
    res[f"bfs-{fabric}-lockstep"] = launch("bfs", fabric, "lockstep")
tight = res["bfs-flat-lockstep"]["bound"] // 3
for mode in ("lockstep", "pipelined"):
    res[f"bfs-flat-{mode}-tight"] = launch("bfs", "flat", mode, cap=tight)
    res[f"bfs-flat-{mode}-tight-e_max"] = launch("bfs", "flat", mode,
                                                 cap=tight, at_e_max=True)
print("RESULT " + json.dumps(res))
"""

APPS = ("bfs", "sssp", "wcc", "pagerank")
MODES = ("lockstep", "pipelined")
FLAT = [f"{app}-flat-{mode}" for app in APPS for mode in MODES]


@pytest.fixture(scope="module")
def launches():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("n_dev", [1, 3, 4])
@pytest.mark.parametrize("undirected", [False, True])
def test_owner_bound_is_the_largest_sender_owner_count(n_dev, undirected):
    from repro.sparse import datasets
    from repro.sparse.program import _graph_setup, _owner_bound
    g = datasets.rmat(9, edge_factor=4, seed=11, undirected=False)
    _, _, dst, _, _ = _graph_setup(g, n_dev, undirected=undirected)
    rows, cols = g.row_of(), g.col_idx.astype(np.int64)
    if undirected:
        rows, cols = np.r_[rows, cols], np.r_[cols, rows]
    want = np.bincount((rows % n_dev) * n_dev + cols % n_dev,
                       minlength=n_dev * n_dev).max()
    assert _owner_bound(dst, n_dev) == want


@pytest.mark.parametrize("case", FLAT)
def test_owner_cap_changes_no_answer(launches, case):
    got, ref = launches[case], launches[case + "-e_max"]
    # every min/store app and PageRank's add alike: bit-identical states
    assert got["states"] == ref["states"]
    assert (got["messages"], got["drops"]) == (ref["messages"], ref["drops"])
    assert got["twin"] == [got["messages"], got["drops"]]
    assert sum(got["drops"]) == 0


@pytest.mark.parametrize("case", FLAT)
def test_wire_plan_and_all_to_all_take_the_owner_cap(launches, case):
    got, ref = launches[case], launches[case + "-e_max"]
    bound = got["bound"]
    assert bound == got["brute_bound"] < got["e_max"]
    (old,) = got["old_caps"]
    assert ref["caps"] == [old] == ref["old_caps"]
    assert got["caps"] == [min(old, max(8, -(-bound // 8) * 8))]
    assert got["caps"][0] < old
    extra = 1 if case.endswith("pipelined") else 0
    assert got["slots"] == [4 * (got["caps"][0] + extra)]
    rows = [r for r, _ in got["a2a"]]
    assert rows == got["slots"] * (1 + extra)


@pytest.mark.parametrize("mode", MODES)
def test_explicit_cap_below_the_bound_drops_as_before(launches, mode):
    got = launches[f"bfs-flat-{mode}-tight"]
    ref = launches[f"bfs-flat-{mode}-tight-e_max"]
    assert got["caps"] == got["old_caps"] == ref["caps"]
    assert sum(got["drops"]) > 0
    assert got["twin"] == [got["messages"], got["drops"]]
    assert (got["messages"], got["drops"]) == (ref["messages"], ref["drops"])
    assert got["states"] == ref["states"]
    assert got["owner_capped_builds"] == 0


@pytest.mark.parametrize("case", FLAT + ["bfs-pods-lockstep",
                                         "bfs-one-lockstep"])
def test_owner_capped_builds_counts_flat_multi_device_shapes(launches,
                                                             case):
    want = 1 if "-flat-" in case else 0
    assert launches[case]["owner_capped_builds"] == want
    if "-flat-" in case:
        assert launches[case + "-e_max"]["owner_capped_builds"] == 0
    else:
        assert launches[case]["caps"] == launches[case]["old_caps"]
