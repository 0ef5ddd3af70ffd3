"""The graph round and the one-round scatter lower to pinned HLO (CPU).

Each callable is built the way a launch builds it (``_build_graph_fn`` /
``_build_scatter_fn`` with the capacities ``resolve_caps`` gives under the
program's default queues) and lowered, not run, from shapes: BFS and
PageRank at the benchmark's one-chip shapes (RMAT-18: n = 262,144,
E_max = 7,590,000), BFS and PageRank on 8 fake devices, flat and as
(2, 4) pods, in both round modes, and the add scatter on the same two
fabrics. Each case keeps two SHA-256 digests: of the HLO text without
debug information (the computation) and of its ``op_name`` metadata in
order (the device scopes a profile reads).

A one-device launch folds the receive-reduce into admission whatever the
round mode says, so its pipelined digest must equal its lockstep one.

Regenerate (only when a graph round or scatter is meant to change)::

    PYTHONPATH=src python tests/test_graph_hlo.py --regen
"""
import json
import os
import subprocess
import sys

import pytest

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "graph_round_hlo.json")

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import hashlib, json, re
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.fabric import Fabric
from repro.core.queues import QueueConfig
from repro.core.routing import resolve_caps
from repro.sparse import program
from repro.sparse.jax_apps import BFS, PAGERANK


def digests(fn, args):
    lowered = fn.lower(*args)
    plain = lowered.as_text(dialect='hlo')
    names = re.findall(r'op_name="([^"]*)"',
                       lowered.as_text(dialect='hlo', debug_info=True))
    return {'hlo': hashlib.sha256(plain.encode()).hexdigest(),
            'op_names': hashlib.sha256('\n'.join(names).encode()).hexdigest(),
            'hlo_chars': len(plain), 'op_name_count': len(names)}


def fabric(shape):
    if shape == (2, 4):
        return Fabric.single(shape, ('pod', 'data')), 'pod'
    return Fabric.single(shape, ('data',)), None


def spec_of(pod_axis):
    return P((pod_axis, 'data')) if pod_axis else P('data')


def graph(prog, shape, n, e_max, params, rounds, round_mode):
    fab, pod_axis = fabric(shape)
    n_dev = fab.n_devices
    n_local = -(-n // n_dev)
    queues = program._resolve_queues(prog, None, None, None)
    caps, pods = resolve_caps(fab, queues, prog.task, e_max, 'data',
                              pod_axis, clamp=True)
    n_states = 3 if prog is PAGERANK else 1
    fn = program._build_graph_fn(
        prog, fab.mesh, 'data', pod_axis, pods, n_dev, n_local, n, caps,
        params, rounds, n_states, round_mode=round_mode)
    sh = NamedSharding(fab.mesh, spec_of(pod_axis))
    edges = [jax.ShapeDtypeStruct((n_dev * e_max,), dt, sharding=sh)
             for dt in (jnp.int32, jnp.int32, jnp.float32)]
    states = [jax.ShapeDtypeStruct((n_dev * n_local,), jnp.float32,
                                   sharding=sh)] * n_states
    return digests(fn, edges + states)


def scatter(shape, n, e):
    fab, pod_axis = fabric(shape)
    n_dev = fab.n_devices
    queues = QueueConfig.from_factor(1.5, 'T3')
    caps, pods = resolve_caps(fab, queues, 'T3', e // n_dev, 'data',
                              pod_axis)
    fn = program._build_scatter_fn(fab.mesh, 'data', pod_axis, pods, n_dev,
                                   -(-n // n_dev), caps, 'add')
    sh = NamedSharding(fab.mesh, spec_of(pod_axis))
    return digests(fn, [jax.ShapeDtypeStruct((e,), jnp.int32, sharding=sh),
                        jax.ShapeDtypeStruct((e,), jnp.float32,
                                             sharding=sh)])


PR = {'damping': 0.85, 'iters': 20}
res = {}
for mode in ('lockstep', 'pipelined'):
    res[f'bfs_one_{mode}'] = graph(BFS, (1,), 262144, 7590000, {}, 128, mode)
res['pagerank_one_lockstep'] = graph(PAGERANK, (1,), 262144, 7590000, PR,
                                     20, 'lockstep')
for shape, tag in (((8,), 'flat8'), ((2, 4), 'pods24')):
    for mode in ('lockstep', 'pipelined'):
        res[f'bfs_{tag}_{mode}'] = graph(BFS, shape, 4096, 8192, {}, 128,
                                         mode)
        res[f'pagerank_{tag}_{mode}'] = graph(PAGERANK, shape, 4096, 8192,
                                              PR, 20, mode)
    res[f'scatter_add_{tag}'] = scatter(shape, 4096, 65536)
print('RESULT ' + json.dumps(res))
"""

CASES = ["bfs_one_lockstep", "bfs_one_pipelined", "pagerank_one_lockstep",
         "bfs_flat8_lockstep", "bfs_flat8_pipelined",
         "pagerank_flat8_lockstep", "pagerank_flat8_pipelined",
         "bfs_pods24_lockstep", "bfs_pods24_pipelined",
         "pagerank_pods24_lockstep", "pagerank_pods24_pipelined",
         "scatter_add_flat8", "scatter_add_pods24"]


def _run_current():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.fixture(scope="module")
def current():
    return _run_current()


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("case", CASES)
def test_graph_round_lowers_to_the_same_hlo(current, golden, case):
    assert current[case] == golden[case]
    if case == "bfs_one_pipelined":
        # one device has no wire to overlap: both modes run one round
        assert current[case] == current["bfs_one_lockstep"]


if __name__ == "__main__":
    if "--regen" in sys.argv:
        res = _run_current()
        with open(GOLDEN, "w") as f:
            json.dump(res, f, indent=1)
        print(f"wrote {GOLDEN}")
