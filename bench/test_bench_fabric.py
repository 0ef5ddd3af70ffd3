"""The four-chip BFS cell on four fake CPU devices, at a small size.

Through the harness's own driver (``bench/drivers/graph_fabric_jobs.py``)
on ``Fabric.fake(4)``, with the cell's configuration cut to scale 11:

* the jobs equal ``bench/ref/graph.bfs`` hop for hop, with no drop;
* the BFS control (queues cut to a quarter) and the ``unchanged`` and
  ``altered`` faults make ``correct`` false;
* ``cross_messages`` equals a brute-force count over the edge list;
* a traced run records ``scope_s`` (empty on the CPU, whose trace has no
  TPU planes) and the wire plan.

And, with no device: ``graph.a2a_roofline`` stays at or below 100 % for
any a2a time at least the least cross-chip bytes over the peak, and every
new reader returns None on a record without its keys, as a program without
the counters and scopes gives.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run as bench_run
from bench import work_fabric

ROOT = Path(__file__).resolve().parents[1]
CELL = "graph500-bfs.4chip"

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import contextlib, json, shutil, time
import jax
from bench import control, run as bench_run
from bench.ref import graph as ref, rmat

SEED = 2**31 + 77
spec = bench_run.load_spec("graph500-bfs.4chip")
spec["config"].update({"scale": 11, "edges": 45000})
driver = bench_run.load_module(bench_run.BENCH / "drivers"
                               / "graph_fabric_jobs.py")


def one(patch=None, trace=False):
    run = bench_run.Run(spec, SEED, 0.3, trace, jax.devices(),
                        time.perf_counter())
    with patch() if patch else contextlib.nullcontext():
        out = driver.run(run)
    if run.trace_dir:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    correct = (all(v <= lim for v, lim in out["checks"].values())
               and out["failed"] == 0)
    return {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "checks": out["checks"],
            "record": out["record"]}


res = {"program": one(),
       "small_queue": one(control.small_queue),
       "unchanged": one(lambda: control.unchanged("graph")),
       "altered": one(lambda: control.altered("graph")),
       "traced": one(trace=True)}

g = rmat.graph(spec["config"], SEED)
nbrs = [g.col_idx[g.row_ptr[u]:g.row_ptr[u + 1]].tolist() for u in range(g.n)]
brute = []
for job in res["program"]["record"]["jobs"]:
    dist = ref.bfs(g, job["param"])
    brute.append(sum(1 for u in range(g.n) if dist[u] >= 0
                     for v in nbrs[u] if u % 4 != v % 4))
res["brute_cross"] = brute
print("RESULT " + json.dumps(res))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(ROOT / "src"), str(ROOT)]), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_jobs_equal_the_oracle_with_no_drop(runs):
    res = runs["program"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert res["checks"] == {"hops_wrong": [0, 0], "drops": [0, 0]}
    assert res["record"]["n_dev"] == 4
    for job in res["record"]["jobs"]:
        assert job["rounds"] >= 2 and job["drops"] == 0


@pytest.mark.parametrize("case", ["small_queue", "unchanged", "altered"])
def test_control_and_faults_are_not_correct(runs, case):
    assert not runs[case]["correct"], runs[case]["checks"]


def test_cross_messages_equal_a_brute_force_count(runs):
    jobs = runs["program"]["record"]["jobs"]
    assert [j["cross_messages"] for j in jobs] == runs["brute_cross"]
    for j in jobs:
        assert 0 < j["cross_messages"] <= sum(j["messages"])


def test_record_carries_the_wire_plan_and_scopes(runs):
    rec = runs["traced"]["record"]
    wire = rec["wire"]
    assert wire["n_dev"] == 4 and len(wire["slots_per_round"]) == 1
    (cap,) = wire["caps"]
    assert wire["slots_per_round"] == [4 * cap]
    assert wire["bytes_per_round"] == 4 * cap * wire["words_per_slot"][0] * 4
    assert rec["scope_s"] == {}        # the CPU trace has no TPU planes
    fill = bench_run.load_module(
        bench_run.BENCH / "metrics" / "graph.wire_fill.py").read(
            rec, None, "cpu")
    assert 0 < fill <= 100


def _reader(name):
    return bench_run.load_module(bench_run.BENCH / "metrics" / f"{name}.py")


NEW_METRICS = ["graph.a2a_ms", "graph.sync_ms", "graph.a2a_roofline",
               "graph.wire_fill"]


def _record(a2a_s=0.5, sync_s=0.01):
    return {"jobs": [{"param": 3, "rounds": 7, "messages": [10] * 7,
                      "drops": 0, "cross_messages": 50},
                     {"param": 5, "rounds": 6, "messages": [20] * 6,
                      "drops": 0, "cross_messages": 90}],
            "n_vertices": 1024, "n_dev": 4,
            "work": {"msg_words": 2, "state_words_read": 1,
                     "state_words_written": 1},
            "scope_s": {"dcra.route.a2a": a2a_s, "dcra.graph.sync": sync_s},
            "wire": {"n_dev": 4, "caps": [64], "slots_per_round": [256],
                     "words_per_slot": [2], "bytes_per_round": 2048}}


def test_new_metrics_read_their_record():
    rec = _record()
    assert _reader("graph.a2a_ms").read(rec, None, "TPU v5 lite") == (
        1e3 * 0.5 / 13)
    assert _reader("graph.sync_ms").read(rec, None, "TPU v5 lite") == (
        1e3 * 0.01 / 13)
    assert _reader("graph.wire_fill").read(rec, None, "TPU v5 lite") == (
        100.0 * 190 / (13 * 4 * 256))
    assert CELL in [w for m in bench_run.load_spec(CELL)["per_layer"]
                    for w in m["workloads"]]


@pytest.mark.parametrize("scale", [1.0, 1.0001, 2.0, 1e3])
def test_a2a_roofline_stays_at_or_below_100(scale):
    need = work_fabric.cross_bytes(50 + 90, 2)
    least_s = need / (4 * work_fabric.ici_peak("TPU v5 lite"))
    share = _reader("graph.a2a_roofline").read(
        _record(a2a_s=least_s * scale), None, "TPU v5 lite")
    assert 0 < share <= 100.0 * (1 + 1e-12)
    assert share == pytest.approx(100.0 / scale)


def test_unknown_device_has_no_interconnect_peak():
    with pytest.raises(KeyError):
        work_fabric.ici_peak("cpu")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_reads_nothing_without_its_keys(name):
    """The parent program records no wire plan and has no
    ``dcra.graph.sync`` scope; a record without ``cross_messages``,
    ``scope_s`` or ``wire`` reads None, and no reader raises."""
    parent = _record()
    del parent["wire"], parent["scope_s"]["dcra.graph.sync"]
    bare = {"jobs": [{"param": 3, "rounds": 7, "messages": [10] * 7,
                      "drops": 0}], "n_vertices": 1024,
            "work": _record()["work"]}
    reader = _reader(name)
    assert reader.read(bare, None, "TPU v5 lite") is None
    assert reader.read({}, None, "TPU v5 lite") is None
    if name in ("graph.sync_ms", "graph.wire_fill"):
        assert reader.read(parent, None, "TPU v5 lite") is None
    else:
        assert reader.read(parent, None, "TPU v5 lite") > 0
