"""Host spans, device scopes and the MoE slot plan (CPU).

* A graph launch writes its host spans — ``dcra.graph.pack`` / ``upload``
  / ``dispatch`` from :func:`launch_program`, ``wait`` / ``transfer`` from
  ``ProgramLaunch.result`` — once each, inside the caller's span, each
  carrying the launch's ordinal.
* The HLO of the graph function (each round shape) and of ``moe_dcra``
  names every device scope its ops run under, so a profile can split the
  time by phase; a refactor that drops one fails here. One-device graph
  rounds, in either round mode, carry no scatter or collective scope and
  are the only ones counted in ``local_fold_builds``; every multi-device
  round shape runs its scalar psums under ``dcra.graph.sync``, inside
  ``dcra.graph.update``.
* ``slot_plan`` gives the bucket sizes ``moe_dcra`` allocates.
"""
import dataclasses
import glob
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

HOST_SPANS = ("dcra.graph.pack", "dcra.graph.upload", "dcra.graph.dispatch",
              "dcra.graph.wait", "dcra.graph.transfer")
GRAPH_PHASES = {"dcra.graph.payload", "dcra.graph.route",
                "dcra.graph.reduce", "dcra.graph.update"}
ROUTE_STEPS = {"dcra.route.rank", "dcra.route.scatter", "dcra.route.a2a"}
MOE_PHASES = {"dcra.moe.router", "dcra.moe.dispatch", "dcra.moe.expert_pad",
              "dcra.moe.expert_ffn", "dcra.moe.combine"}


def _graph():
    from repro.sparse import datasets
    return datasets.rmat(7, edge_factor=4, seed=3)


def _fabric():
    from repro.core.fabric import Fabric
    return Fabric.single((1,), ("data",))


def _launch(app):
    from repro.sparse import program
    from repro.sparse.jax_apps import BFS, PAGERANK
    if app == "bfs":
        return program.launch_program(BFS, _graph(), _fabric(),
                                      params={"root": 0})
    return program.launch_program(PAGERANK, _graph(), _fabric(),
                                  params={"damping": 0.85, "iters": 3})


@pytest.mark.parametrize("app", ["bfs", "pagerank"])
def test_graph_launch_writes_each_host_span_once(app, tmp_path):
    import jax
    from jax.profiler import ProfileData
    _launch(app).result()                    # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("caller"):
            job = _launch(app)
            job.result()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    events = [(line.name, e) for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:") for line in plane.lines
              for e in line.events]
    (caller_line, caller), = [(ln, e) for ln, e in events
                              if e.name == "caller"]
    ours = [(ln, e) for ln, e in events if e.name.startswith("dcra.")]
    assert sorted(e.name for _, e in ours) == sorted(HOST_SPANS)
    end = caller.start_ns + caller.duration_ns
    for line, e in ours:
        assert line == caller_line
        assert caller.start_ns <= e.start_ns
        assert e.start_ns + e.duration_ns <= end
        assert dict(e.stats)["launch"] == job.launch
    # the host spans run in order: pack, upload, dispatch, wait, transfer
    by_start = [e.name for _, e in sorted(ours, key=lambda le: le[1].start_ns)]
    assert by_start == list(HOST_SPANS)


def test_launch_ordinals_increase():
    a, b = _launch("bfs"), _launch("bfs")
    assert b.launch > a.launch
    a.result(), b.result()


# the scope names in the HLO metadata of each layer shape, on 4 fake
# devices so that every collective is there (one device elides them)
SCOPES_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, json, re
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.compat import make_mesh
from repro.core.dispatch import MeshInfo, moe_dcra
from repro.core.fabric import Fabric
from repro.models.moe import init_moe
from repro.sparse import LaunchOptions, datasets, program
from repro.sparse.jax_apps import BFS, PAGERANK


def scopes(text):
    return sorted({p for st in re.findall(r'op_name="([^"]*)"', text)
                   for p in st.split("/") if p.startswith("dcra.")})


FOLDS = {}
SYNC_PARENTS = {}


def graph(app, round_mode, shape, names, pod_axis):
    texts, build = [], program._build_graph_fn
    folds0 = program.cache_stats()["local_fold_builds"]

    def spy(*a, **kw):
        fn = build(*a, **kw)

        def call(*args):
            texts.append(fn.lower(*args).as_text(dialect="hlo",
                                                 debug_info=True))
            return fn(*args)
        return call

    program._build_graph_fn, program._CACHE = spy, {}
    prog, params = ((BFS, {"root": 0}) if app == "bfs" else
                    (PAGERANK, {"damping": 0.85, "iters": 3}))
    program.run_program(
        prog, datasets.rmat(7, edge_factor=4, seed=3),
        Fabric.single(shape, names), params=params,
        options=LaunchOptions(axis="data", pod_axis=pod_axis,
                              round_mode=round_mode))
    program._build_graph_fn = build
    (text,) = texts
    FOLDS[(app, round_mode, shape)] = (
        program.cache_stats()["local_fold_builds"] - folds0)
    SYNC_PARENTS[f"{app}-{round_mode}-{list(shape)}"] = sorted(
        {ps[ps.index("dcra.graph.sync") - 1]
         for st in re.findall(r'op_name="([^"]*)"', text)
         for ps in [[p for p in st.split("/") if p.startswith("dcra.")]]
         if "dcra.graph.sync" in ps})
    return scopes(text)


def moe(n_experts, shape, names, pod_axis, share=None, **moe_fields):
    cfg = get_config("olmoe-1b-7b").reduced()
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=n_experts, top_k=1, **moe_fields))
    info = MeshInfo(make_mesh(shape, names), pod_axis=pod_axis,
                    expert_share=share)
    params = init_moe(jax.random.key(0), cfg)
    if share:
        params.update({k: params[k][share[0]:sum(share)]
                       for k in ("wg", "wu", "wd")})
    x = jnp.ones((2, 8, cfg.d_model), jnp.float32)
    return scopes(jax.jit(lambda p, x: moe_dcra(p, x, cfg, info)).lower(
        params, x).as_text(dialect="hlo", debug_info=True))


flat, pods = ((4,), ("data",), None), ((2, 2), ("pod", "data"), "pod")
one = ((1,), ("data",), None)
moe_mesh = ((1, 4, 1), ("data", "expert", "tp"), None)
moe_pods = ((2, 1, 2, 1), ("pod", "data", "expert", "tp"), "pod")
res = {
    "graph-bfs-lockstep": graph("bfs", "lockstep", *flat),
    "graph-bfs-lockstep-pods": graph("bfs", "lockstep", *pods),
    "graph-bfs-pipelined": graph("bfs", "pipelined", *flat),
    "graph-bfs-pipelined-pods": graph("bfs", "pipelined", *pods),
    "graph-bfs-pipelined-one-device": graph("bfs", "pipelined", *one),
    "graph-bfs-lockstep-one-device": graph("bfs", "lockstep", *one),
    "graph-pagerank-lockstep": graph("pagerank", "lockstep", *flat),
    "graph-pagerank-pipelined": graph("pagerank", "pipelined", *flat),
    "graph-pagerank-lockstep-one-device": graph("pagerank", "lockstep",
                                                *one),
    "moe": moe(8, *moe_mesh),
    "moe-one-expert-per-shard": moe(4, *moe_mesh),
    "moe-pods": moe(8, *moe_pods),
    "moe-share-shared-expert": moe(16, *moe_mesh, share=(8, 8),
                                   scoring="sigmoid", n_group=4, topk_group=2,
                                   n_shared=1, d_shared=32),
}
res["local_fold_builds"] = [[list(k[:2]) + [list(k[2])], v]
                            for k, v in FOLDS.items()]
res["sync_parents"] = SYNC_PARENTS
print("RESULT " + json.dumps(res))
"""

# several devices: the round's scalar psums run under their own scope
EVERY_GRAPH = GRAPH_PHASES | ROUTE_STEPS | {"dcra.graph.sync"}
EVERY_MOE = MOE_PHASES | ROUTE_STEPS
EXPECTED_SCOPES = {
    "graph-bfs-lockstep": EVERY_GRAPH,
    "graph-bfs-lockstep-pods": EVERY_GRAPH,
    "graph-bfs-pipelined": EVERY_GRAPH,
    "graph-bfs-pipelined-pods": EVERY_GRAPH,
    # one device, any round mode or reduce op: the route only ranks, the
    # reduce reads the edge stream, nothing is scattered or crosses a
    # wire, and the psums over one device have no sync scope
    "graph-bfs-pipelined-one-device": GRAPH_PHASES | {"dcra.route.rank"},
    "graph-bfs-lockstep-one-device": GRAPH_PHASES | {"dcra.route.rank"},
    "graph-pagerank-lockstep": EVERY_GRAPH,
    "graph-pagerank-pipelined": EVERY_GRAPH,
    "graph-pagerank-lockstep-one-device": GRAPH_PHASES | {"dcra.route.rank"},
    "moe": EVERY_MOE,
    # one expert per shard: no per-expert bucket to pad
    "moe-one-expert-per-shard": EVERY_MOE - {"dcra.moe.expert_pad"},
    "moe-pods": EVERY_MOE,
    # a share of sigmoid-routed experts with a shared expert beside them
    "moe-share-shared-expert": EVERY_MOE | {"dcra.moe.shared"},
}


@pytest.fixture(scope="module")
def hlo_scopes():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCOPES_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("case", sorted(EXPECTED_SCOPES))
def test_layer_hlo_carries_every_scope(case, hlo_scopes):
    assert set(hlo_scopes[case]) == EXPECTED_SCOPES[case]


def test_sync_scope_nests_in_update_on_several_devices(hlo_scopes):
    """``dcra.graph.sync`` holds the scalar psums of every multi-device
    round shape, directly inside ``dcra.graph.update``, and appears in no
    one-device round."""
    parents = hlo_scopes["sync_parents"]
    assert len(parents) == 9
    for case, scopes in parents.items():
        assert scopes == ([] if case.endswith("[1]") else
                          ["dcra.graph.update"]), case


def test_local_fold_builds_only_on_one_device(hlo_scopes):
    """Each graph callable built on the one-device fabric takes the fold;
    none built on the four-device flat or 2x2 pod fabric does."""
    builds = hlo_scopes["local_fold_builds"]
    assert len(builds) == 9
    assert sum(n for (*_, shape), n in builds if shape == [1]) == 3
    for (app, mode, shape), n in builds:
        assert n == (1 if shape == [1] else 0), (app, mode, shape)


def _moe(capacity_factor=1.25, num_experts=4, top_k=2):
    import jax
    from repro.configs import get_config
    from repro.core.compat import make_mesh
    from repro.core.dispatch import MeshInfo
    from repro.models.moe import init_moe
    cfg = get_config("olmoe-1b-7b").reduced()
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=capacity_factor, num_experts=num_experts,
        top_k=top_k))
    info = MeshInfo(make_mesh((1, 1, 1), ("data", "expert", "tp")),
                    pod_axis=None)
    return cfg, info, init_moe(jax.random.key(0), cfg)


@pytest.mark.parametrize("batch,seq,capacity_factor,num_experts,top_k", [
    (2, 16, 1.25, 4, 2),
    (1, 24, 2.0, 4, 2),
    (4, 32, 2.0, 8, 3),
    (2, 8, 1.0, 1, 1),
])
def test_slot_plan_is_what_moe_dcra_allocates(batch, seq, capacity_factor,
                                              num_experts, top_k,
                                              monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.core import dispatch
    cfg, info, params = _moe(capacity_factor, num_experts, top_k)
    buckets, ffn_rows = [], []
    bucket, ffn = dispatch._bucket, dispatch._expert_ffn

    def bucket_spy(x, dest, valid, aux, n_buckets, cap):
        buckets.append((n_buckets, cap))
        return bucket(x, dest, valid, aux, n_buckets, cap)

    def ffn_spy(xe, *a):
        ffn_rows.append(xe.shape[0] * xe.shape[1])
        return ffn(xe, *a)

    monkeypatch.setattr(dispatch, "_bucket", bucket_spy)
    monkeypatch.setattr(dispatch, "_expert_ffn", ffn_spy)
    x = jnp.ones((batch, seq, cfg.d_model), jnp.float32)
    jax.eval_shape(lambda p, x: dispatch.moe_dcra(p, x, cfg, info),
                   params, x)
    plan = dispatch.slot_plan(cfg.moe, info, batch * seq)
    assert plan.tasks == batch * seq * top_k
    assert buckets[0] == (1, plan.cap1)
    assert plan.dispatch_slots == plan.cap1
    assert ffn_rows == [plan.expert_slots]
    if num_experts > 1:
        assert buckets[1:] == [(num_experts, plan.cap_e)]
    assert plan.slot_fill == plan.tasks / plan.expert_slots


def test_slot_fill_of_one_chip_olmoe_layer():
    """4096 tokens, top-8 of 64 experts at capacity factor 2 on one chip:
    the dispatch bucket doubles the 32768 tasks, the expert bucket
    doubles them again, so a quarter of the expert rows hold a task."""
    from repro.core import dispatch
    cfg, info, _ = _moe(capacity_factor=2.0, num_experts=64, top_k=8)
    plan = dispatch.slot_plan(cfg.moe, info, 4096)
    assert (plan.tasks, plan.dispatch_slots, plan.expert_slots) == (
        32768, 65536, 131072)
    assert plan.slot_fill == 0.25
