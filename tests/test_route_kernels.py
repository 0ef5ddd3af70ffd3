"""Differential tests for the routing rank (:mod:`repro.kernels.route`).

Part A — in-process: the rank's lowerings (the Mosaic kernel in interpret
mode, the XLA tile-scan) and the deployed ``bucket`` must agree
bit-exactly with the one-hot rank and a numpy admission oracle on
awkward (prime) sizes.

Part B — distributed (subprocess, 8 host devices): the deployed
``owner_route`` recv/drop streams on 1/2/4/8 devices must equal a numpy
oracle of first-``cap``-per-channel admission, and pod/portal launches
must match the analytic twin's per-round stats, under tight caps that
actually drop — which is what keeps the analytic twins exact.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core.routing import bucket, positions_by_dest, reduce_received
from repro.kernels.route import (bucket_rank_pallas, bucket_rank_xla,
                                 onehot_rank)
from repro.sparse.options import LaunchOptions
from repro.sparse.program import cache_stats, clear_cache


# ---------------------------------------------------------------------------
# Part A: the rank's lowerings vs the one-hot rank and a numpy oracle
# ---------------------------------------------------------------------------

def _bucket_oracle(x, dest, valid, aux, n_buckets, cap):
    """numpy ``bucket``: the first ``cap`` valid tasks per destination in
    array order, each in slot ``dest * cap + rank``."""
    x, dest, valid = np.asarray(x), np.asarray(dest), np.asarray(valid)
    total = n_buckets * cap
    xb = np.zeros((total,) + x.shape[1:], x.dtype)
    ints = [np.full(total, -1, np.int32) for _ in aux]
    task_slot = np.full(len(dest), -1, np.int32)
    seen = np.zeros(n_buckets, np.int64)
    for i in np.flatnonzero(valid):
        d = dest[i]
        if seen[d] < cap:
            slot = d * cap + seen[d]
            xb[slot] = x[i]
            for col, a in zip(ints, aux):
                col[slot] = np.asarray(a)[i]
            task_slot[i] = slot
        seen[d] += 1
    return xb, ints, task_slot, int(valid.sum() - (task_slot >= 0).sum())


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       n=st.sampled_from([1, 7, 61, 127, 509]),
       n_buckets=st.sampled_from([1, 3, 8, 37, 64]))
def test_rank_kernels_match_onehot(seed, n, n_buckets):
    rng = np.random.default_rng(seed)
    dest = jnp.asarray(rng.integers(0, n_buckets, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.8)
    want = onehot_rank(dest, valid, n_buckets)
    for name, got in [
            ("pallas-interpret", bucket_rank_pallas(dest, valid, n_buckets)),
            ("xla-tilescan", bucket_rank_xla(dest, valid, n_buckets)),
            ("deployed", positions_by_dest(dest, valid, n_buckets))]:
        assert bool(jnp.all(jnp.where(valid, got == want, True))), name


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       n=st.sampled_from([5, 127, 509]),
       n_buckets=st.sampled_from([2, 7, 32]),
       cap=st.sampled_from([1, 3, 8]))
def test_bucket_impls_bit_identical(seed, n, n_buckets, cap):
    """(xb, ints, task_slot, n_drop) of the deployed ``bucket`` — the
    one-hot rank below 32 buckets off TPU, the tile-scan at 32 — must
    agree elementwise with the numpy admission oracle, 1-D payloads
    included."""
    rng = np.random.default_rng(seed)
    dest = jnp.asarray(rng.integers(0, n_buckets, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.85)
    aux = [jnp.asarray(rng.integers(0, 1000, n), jnp.int32),
           jnp.asarray(rng.integers(0, 50, n), jnp.int32)]
    for shape in ((n, 2), (n,)):
        x = jnp.asarray(rng.random(shape), jnp.float32)
        got = bucket(x, dest, valid, aux, n_buckets, cap)
        want = _bucket_oracle(x, dest, valid, aux, n_buckets, cap)
        assert np.array_equal(np.asarray(got[0]), want[0]), shape
        for a, b in zip(got[1], want[1]):
            assert np.array_equal(np.asarray(a), b), shape
        assert np.array_equal(np.asarray(got[2]), want[2]), shape
        assert int(got[3]) == want[3], shape


def test_empty_streams_are_safe():
    """N=0 must not build a zero-size pallas grid (regression): the rank
    kernel and the histogram kernel early-return their identity, and the
    deployed bucket and receive-reduce give empty queues."""
    from repro.kernels.histogram import histogram_pallas
    empty_i = jnp.zeros((0,), jnp.int32)
    empty_b = jnp.zeros((0,), bool)
    assert bucket_rank_pallas(empty_i, empty_b, 4).shape == (0,)
    xb, ints, slot, nd = bucket(jnp.zeros((0, 1), jnp.float32), empty_i,
                                empty_b, [empty_i], 4, 2)
    assert xb.shape == (8, 1) and not bool(jnp.any(xb))
    assert ints[0].tolist() == [-1] * 8
    assert slot.shape == (0,) and int(nd) == 0
    for op, fill in (("add", 0.0), ("min", np.inf), ("store", 0.0)):
        got = reduce_received(empty_i, jnp.zeros((0,)), 5, op)
        assert got.tolist() == [fill] * 5, op
    assert histogram_pallas(empty_i, 5).tolist() == [0] * 5


def test_histogram_kernel_matches_reduce_received():
    """The single-shard local-reduce glue: the histogram kernel must
    equal the routed receive-reduce over the same task stream."""
    from repro.kernels import ops
    rng = np.random.default_rng(3)
    n, bins = 997, 61                            # primes: tail-pad path
    dest = rng.integers(0, bins, n)
    dest[rng.random(n) < 0.1] = -1               # padding no-tasks
    slots = jnp.asarray(dest, jnp.int32)
    want = reduce_received(slots, jnp.ones(n, jnp.float32), bins, "add")
    got = ops.histogram(slots, bins).astype(jnp.float32)
    assert jnp.array_equal(want, got)


def test_histogram_local_reduce_end_to_end():
    """Single-shard ``dcra_histogram`` engages the kernel local reduce
    (no-drop guard holds: default factor 2.0 can never drop on one
    shard) and must equal the routed path bit-for-bit."""
    from repro.core.compat import make_mesh
    from repro.sparse.jax_apps import (dcra_histogram, dcra_scatter,
                                       from_owner_layout,
                                       histogram_task_stream)
    rng = np.random.default_rng(7)
    els = rng.integers(0, 53, 811)               # primes: off-tile tails
    mesh = make_mesh((1,), ("data",))
    clear_cache()
    y_kernel, d_kernel = dcra_histogram(els, 53, mesh)
    assert cache_stats()["misses"] == 0          # no scatter compiled: the
    #                                            # kernel path really ran
    dest, vals = histogram_task_stream(els, 1)
    y_sh, d_routed = dcra_scatter(
        jnp.asarray(dest), jnp.asarray(vals), 53, mesh,
        options=LaunchOptions(capacity_factor=2.0))
    assert cache_stats()["misses"] == 1          # the routed scatter
    y_routed = from_owner_layout(y_sh, 53, 1)
    assert d_kernel == 0 and int(d_routed) == 0
    assert np.array_equal(np.asarray(y_kernel), np.asarray(y_routed))
    assert int(np.asarray(y_kernel).sum()) == 811


# ---------------------------------------------------------------------------
# Part B: identical recv/drop streams on 1/2/4/8 devices, flat + pod/portal
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.compat import make_mesh, shard_map_unchecked
from repro.core.routing import owner_route
from repro.sparse.options import LaunchOptions
from repro.sparse.program import program_app_stats, run_program
from repro.sparse.jax_apps import BFS

results = []


def flat_oracle(dest, vals, n_dev, e_local, cap):
    # sender s keeps its first `cap` valid tasks per owner o, in array
    # order; the tiled all_to_all hands owner o block s of cap entries
    rs = np.full((n_dev, n_dev, cap), -1, np.int64)
    rv = np.zeros((n_dev, n_dev, cap), np.float32)
    drops = 0
    for s in range(n_dev):
        seen = np.zeros(n_dev, np.int64)
        for i in range(s * e_local, (s + 1) * e_local):
            if dest[i] < 0:
                continue
            o = dest[i] % n_dev
            if seen[o] < cap:
                rs[o, s, seen[o]] = dest[i] // n_dev
                rv[o, s, seen[o]] = vals[i]
            else:
                drops += 1
            seen[o] += 1
    return rs.reshape(-1), rv.reshape(-1), drops


# --- flat: raw recv/drop streams from owner_route, elementwise ----------
for n_dev in (1, 2, 4, 8):
    mesh = make_mesh((n_dev,), ('data',))
    rng = np.random.default_rng(n_dev)
    e_local = 64
    E = e_local * n_dev
    n = 40
    dest = rng.integers(0, n, E).astype(np.int32)
    dest[rng.random(E) < 0.15] = -1
    vals = rng.random(E).astype(np.float32)
    cap = 8                                       # tight: forces drops

    def k(d_b, v_b):
        valid = d_b >= 0
        d_c = jnp.maximum(d_b, 0)
        rs, rv, nd = owner_route(v_b, d_c // n_dev, d_c % n_dev,
                                 valid, n_dev, cap, 'data')
        return rs, rv, jax.lax.psum(nd, 'data')
    f = jax.jit(shard_map_unchecked(k, mesh=mesh,
                                    in_specs=(P('data'), P('data')),
                                    out_specs=(P('data'), P('data'), P())))
    rs, rv, nd = f(jnp.asarray(dest), jnp.asarray(vals))
    want = flat_oracle(dest, vals, n_dev, e_local, cap)
    ok = (np.array_equal(np.asarray(rs), want[0])
          and np.array_equal(np.asarray(rv), want[1]) and int(nd) == want[2])
    results.append({'case': f'flat n_dev={n_dev}', 'identical': ok,
                    'drops': want[2]})

# --- pod/portal: per-round stats against the analytic twin, tight caps --
from repro.sparse.datasets import rmat
g = rmat(7, edge_factor=4, seed=5)
for shape, axes in [((2, 2), ('pod', 'data')), ((2, 4), ('pod', 'data'))]:
    mesh = make_mesh(shape, axes)
    _, stats = run_program(
        BFS, g, mesh,
        options=LaunchOptions(pod_axis='pod', capacity_factor=0.5),
        params={'root': 0})
    twin = program_app_stats(BFS, g, shape[0] * shape[1],
                             capacity_factor=0.5, params={'root': 0},
                             pods=(shape[1], shape[0]))
    ok = (stats.messages.tolist() == twin.messages.tolist()
          and stats.drops.tolist() == twin.drops.tolist())
    results.append({'case': f'hier {shape}', 'identical': ok,
                    'drops': int(stats.drops.sum())})

print('RESULT ' + json.dumps(results))
"""


@pytest.fixture(scope="module")
def dist_cases():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_all_impls_identical_streams(dist_cases):
    bad = [c for c in dist_cases if not c["identical"]]
    assert not bad, bad


def test_distributed_cases_cover_drops(dist_cases):
    """Tight caps must actually exercise the overflow path."""
    assert any(c["drops"] > 0 for c in dist_cases)
    assert len(dist_cases) == 4 + 2
