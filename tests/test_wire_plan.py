"""The wire plan and the per-shard state upload of a graph launch (CPU).

* ``program.wire_plan`` gives, per stage, the rows and int32 columns of
  the all-to-all operand that the launch's lowered HLO holds, on 4 fake
  devices: flat (4,) and (2, 2) pods, lockstep and pipelined, BFS and
  PageRank (the pipelined shape issues each stage twice in the HLO, once
  before the loop and once in it). A one-device launch folds its round:
  no all-to-all, an empty plan.
* Every argument of the jitted call, the packed initial states included,
  arrives laid out as ``NamedSharding(fab.mesh, spec)``, and the results
  are bit-identical to those of states handed over whole (``jnp.asarray``)
  for jit to reshard.
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.fabric import Fabric
from repro.sparse import LaunchOptions, datasets, program
from repro.sparse.jax_apps import BFS, PAGERANK

G = datasets.rmat(8, edge_factor=4, seed=3)
FABRICS = {"flat": ((4,), ("data",), None), "pods": ((2, 2), ("pod", "data"), "pod"),
           "one": ((1,), ("data",), None)}
APPS = {"bfs": (BFS, {"root": 0}), "pagerank": (PAGERANK, {"damping": 0.85, "iters": 3})}
A2A = re.compile(r"= s32\[(\d+),(\d+)\]\{[^}]*\} all-to-all\(")


def launch(app, fabric, mode):
    prog, params = APPS[app]
    shape, names, pod_axis = FABRICS[fabric]
    fab = Fabric.single(shape, names)
    opts = LaunchOptions(axis="data", pod_axis=pod_axis, round_mode=mode)
    seen, build = [], program._build_graph_fn

    def spy(*a, **kw):
        fn = build(*a, **kw)

        def call(*args):
            seen.append((fn.lower(*args).as_text(dialect="hlo"),
                         [a.sharding for a in args]))
            return fn(*args)
        return call

    program._build_graph_fn, program._CACHE = spy, {}
    try:
        states, stats = program.run_program(prog, G, fab, params=params,
                                            options=opts)
    finally:
        program._build_graph_fn = build
    (text, shardings), = seen
    spec = P((pod_axis, "data")) if pod_axis else P("data")
    want = NamedSharding(fab.mesh, spec)
    plan = program.wire_plan(prog, G, fab, options=opts)

    # the parent's hand-over: states whole, for jit to reshard
    to_global = program._to_global
    program._to_global = lambda fab, spec, arr: jnp.asarray(arr)
    try:
        whole, whole_stats = program.run_program(prog, G, fab, params=params,
                                                 options=opts)
    finally:
        program._to_global = to_global
    return {"a2a": [[int(r), int(c)] for r, c in A2A.findall(text)],
            "plan": plan._asdict(),
            "laid_out": [s == want for s in shardings],
            "same_as_whole": (all(np.array_equal(a, b)
                                  for a, b in zip(states, whole))
                              and np.array_equal(stats.messages,
                                                 whole_stats.messages)
                              and np.array_equal(stats.drops,
                                                 whole_stats.drops))}


res = {f"{app}-{fabric}-{mode}": launch(app, fabric, mode)
       for app in APPS for fabric in FABRICS
       for mode in ("lockstep", "pipelined")}
print("RESULT " + json.dumps(res))
"""

CASES = [f"{app}-{fabric}-{mode}" for app in ("bfs", "pagerank")
         for fabric in ("flat", "pods", "one")
         for mode in ("lockstep", "pipelined")]


@pytest.fixture(scope="module")
def launches():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("case", CASES)
def test_wire_plan_is_the_all_to_all_operands(launches, case):
    got = launches[case]
    plan = got["plan"]
    stages = [[r, w] for r, w in zip(plan["slots_per_round"],
                                     plan["words_per_slot"])]
    if case.split("-")[1] == "one":
        assert got["a2a"] == [] and stages == []
        assert plan["bytes_per_round"] == 0
        return
    assert len(stages) == (2 if "-pods-" in case else 1)
    issued = 2 if case.endswith("pipelined") else 1
    assert got["a2a"] == stages * issued
    assert plan["bytes_per_round"] == sum(4 * r * w for r, w in stages)
    assert plan["n_dev"] == 4


@pytest.mark.parametrize("case", CASES)
def test_states_arrive_laid_out_on_the_mesh(launches, case):
    got = launches[case]
    assert got["laid_out"] == [True] * len(got["laid_out"])
    assert len(got["laid_out"]) == (6 if case.startswith("pagerank") else 4)
    assert got["same_as_whole"]
