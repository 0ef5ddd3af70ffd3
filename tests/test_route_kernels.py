"""Differential tests for the Pallas routing fast path
(:mod:`repro.kernels.route` + the ``route_impl`` knob).

Part A — in-process: the raw interpret-mode kernels (bucket-rank, fused
bucket-scatter, receive-reduce) and both XLA renderings must agree
bit-exactly with the legacy one-hot primitives on awkward (prime) sizes.

Part B — distributed (subprocess, 8 host devices): all three impls must
produce *identical* recv/drop streams on 1/2/4/8 devices, flat and
pod/portal, under tight caps that actually drop — which is what keeps
the analytic twins exact no matter which impl a launch resolves.
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
                                 bucket_scatter_pallas,
                                 reduce_received_pallas, resolve_route_impl)
from repro.sparse.program import cache_stats, clear_cache


# ---------------------------------------------------------------------------
# Part A: kernels vs the one-hot oracle primitives
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       n=st.sampled_from([1, 7, 61, 127, 509]),
       n_buckets=st.sampled_from([1, 3, 8, 37, 64]))
def test_rank_kernels_match_onehot(seed, n, n_buckets):
    rng = np.random.default_rng(seed)
    dest = jnp.asarray(rng.integers(0, n_buckets, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.8)
    want = positions_by_dest(dest, valid, n_buckets, impl="onehot")
    for name, got in [
            ("pallas-interpret", bucket_rank_pallas(dest, valid, n_buckets)),
            ("xla-tilescan", bucket_rank_xla(dest, valid, n_buckets)),
            ("sort", positions_by_dest(dest, valid, n_buckets, impl="sort"))]:
        assert bool(jnp.all(jnp.where(valid, got == want, True))), name


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       n=st.sampled_from([5, 127, 509]),
       n_buckets=st.sampled_from([2, 7, 32]),
       cap=st.sampled_from([1, 3, 8]))
def test_bucket_impls_bit_identical(seed, n, n_buckets, cap):
    """(xb, ints, task_slot, n_drop) must agree elementwise across the
    one-hot / sort / tile-scan impls AND the fused interpret kernel."""
    rng = np.random.default_rng(seed)
    dest = jnp.asarray(rng.integers(0, n_buckets, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.85)
    x = jnp.asarray(rng.random((n, 2)), jnp.float32)
    aux = [jnp.asarray(rng.integers(0, 1000, n), jnp.int32),
           jnp.asarray(rng.integers(0, 50, n), jnp.int32)]
    outs = {impl: bucket(x, dest, valid, aux, n_buckets, cap, impl=impl)
            for impl in ("onehot", "sort", "pallas")}
    outs["fused-kernel"] = bucket_scatter_pallas(x, dest, valid, aux,
                                                 n_buckets, cap)
    ref = outs.pop("onehot")
    for name, got in outs.items():
        assert jnp.array_equal(ref[0], got[0]), name
        for a, b in zip(ref[1], got[1]):
            assert jnp.array_equal(a, b), name
        assert jnp.array_equal(ref[2], got[2]), name
        assert int(ref[3]) == int(got[3]), name


def test_bucket_sort_gather_matches_onehot():
    """The gather-based sort bucketing: ``xb``/aux come straight off the
    stable argsort (slot (b, p) gathers sorted position start[b] + p)
    instead of a second segment-sum scatter. Must be bit-identical to the
    one-hot reference on prime sizes — including 1-D payload squeeze,
    aux columns, task_slot and the first-cap-per-channel drop count."""
    from repro.kernels.route import bucket_sort_gather
    for seed, n, n_buckets, cap in [(0, 7, 3, 2), (1, 101, 13, 3),
                                    (2, 499, 31, 1), (3, 17, 5, 8)]:
        rng = np.random.default_rng(seed)
        dest = jnp.asarray(rng.integers(0, n_buckets, n), jnp.int32)
        valid = jnp.asarray(rng.random(n) < 0.8)
        aux = [jnp.asarray(rng.integers(0, 999, n), jnp.int32)]
        for shape in ((n, 3), (n,)):
            x = jnp.asarray(rng.random(shape), jnp.float32)
            want = bucket(x, dest, valid, aux, n_buckets, cap,
                          impl="onehot")
            got = bucket_sort_gather(x, dest, valid, aux, n_buckets, cap)
            assert got[0].shape == want[0].shape
            assert jnp.array_equal(want[0], got[0]), (seed, shape)
            assert jnp.array_equal(want[1][0], got[1][0]), (seed, shape)
            assert jnp.array_equal(want[2], got[2]), (seed, shape)
            assert int(want[3]) == int(got[3]), (seed, shape)
    # empty stream: identity outputs, no zero-size sort
    e_i = jnp.zeros((0,), jnp.int32)
    xb, ints, slot, nd = bucket_sort_gather(
        jnp.zeros((0, 2), jnp.float32), e_i, jnp.zeros((0,), bool),
        [e_i], 4, 2)
    assert xb.shape == (8, 2) and ints[0].shape == (8,)
    assert slot.shape == (0,) and int(nd) == 0


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), op=st.sampled_from(["add", "min",
                                                           "store"]))
def test_reduce_kernel_matches_segment_ops(seed, op):
    rng = np.random.default_rng(seed)
    n, m = int(rng.integers(3, 400)), int(rng.integers(2, 60))
    slots = jnp.asarray(rng.integers(-1, m, n), jnp.int32)
    vals = jnp.asarray(rng.random(n) * 20 - 10, jnp.float32)
    want = reduce_received(slots, vals, m, op)
    got = reduce_received_pallas(slots, vals, m, op)
    assert jnp.array_equal(want, got), op


def test_empty_streams_are_safe():
    """N=0 must not build a zero-size pallas grid (regression): every
    kernel wrapper early-returns its identity, matching the XLA paths."""
    from repro.kernels.histogram import histogram_pallas
    empty_i = jnp.zeros((0,), jnp.int32)
    empty_b = jnp.zeros((0,), bool)
    assert bucket_rank_pallas(empty_i, empty_b, 4).shape == (0,)
    xb, ints, slot, nd = bucket_scatter_pallas(
        jnp.zeros((0, 1), jnp.float32), empty_i, empty_b, [empty_i], 4, 2)
    want_xb, want_ints, want_slot, want_nd = bucket(
        jnp.zeros((0, 1), jnp.float32), empty_i, empty_b, [empty_i], 4, 2,
        impl="onehot")
    assert jnp.array_equal(xb, want_xb)
    assert jnp.array_equal(ints[0], want_ints[0])
    assert slot.shape == (0,) and int(nd) == int(want_nd) == 0
    for op in ("add", "min", "store"):
        got = reduce_received_pallas(empty_i, jnp.zeros((0,)), 5, op)
        want = reduce_received(empty_i, jnp.zeros((0,)), 5, op)
        assert jnp.array_equal(got, want), op
    assert histogram_pallas(empty_i, 5).tolist() == [0] * 5


def test_resolve_route_impl():
    assert resolve_route_impl(None) == "pallas"
    assert resolve_route_impl("auto") == "pallas"
    assert resolve_route_impl("sort") == "sort"
    with pytest.raises(ValueError):
        resolve_route_impl("quantum")


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
    from repro.sparse.jax_apps import dcra_histogram
    rng = np.random.default_rng(7)
    els = rng.integers(0, 53, 811)               # primes: off-tile tails
    mesh = make_mesh((1,), ("data",))
    clear_cache()
    y_kernel, d_kernel = dcra_histogram(els, 53, mesh)
    assert cache_stats()["misses"] == 0          # no scatter compiled: the
    #                                            # kernel path really ran
    y_routed, d_routed = dcra_histogram(els, 53, mesh, route_impl="onehot",
                                        capacity_factor=2.0)
    assert cache_stats()["misses"] == 1          # explicit impl: routed
    assert d_kernel == 0 and d_routed == 0
    assert np.array_equal(np.asarray(y_kernel), np.asarray(y_routed))
    assert int(np.asarray(y_kernel).sum()) == 811


def test_route_compare_gate():
    """The CI trajectory gate: speedup-relative (machine-portable),
    >tol relative drop or silent coverage loss fails."""
    from repro.dse.route_compare import compare
    cell = {"n": 65536, "s": 64, "cap": 2048,
            "ms": {"onehot": 50.0, "sort": 25.0, "pallas": 10.0},
            "speedup_vs_onehot": {"onehot": 1.0, "sort": 2.0,
                                  "pallas": 5.0}}
    old = {"schema": "dcra-route-bench/v1", "cells": [cell]}
    f, _ = compare(old, old)
    assert not f
    worse = json.loads(json.dumps(old))
    worse["cells"][0]["speedup_vs_onehot"]["pallas"] = 3.9   # -22%
    f, _ = compare(old, worse)
    assert f and "REGRESSED" in f[0]
    f, _ = compare(old, worse, tol=0.25)                     # within 25%
    assert not f
    gone = {"schema": "dcra-route-bench/v1", "cells": []}
    f, _ = compare(old, gone)
    assert f


def test_route_impl_is_part_of_compile_cache_key():
    from repro.core.compat import make_mesh
    from repro.sparse.jax_apps import dcra_scatter
    mesh = make_mesh((1,), ("data",))
    dest = jnp.asarray(np.arange(16) % 4, jnp.int32)
    vals = jnp.ones(16, jnp.float32)
    clear_cache()
    ys = {}
    for impl in ("onehot", "sort", "pallas"):
        y, _ = dcra_scatter(dest, vals, 4, mesh, route_impl=impl)
        ys[impl] = np.asarray(y)
    assert cache_stats()["misses"] == 3          # one compile per impl
    y, _ = dcra_scatter(dest, vals, 4, mesh, route_impl="sort")
    assert cache_stats()["hits"] == 1            # repeat launch: no re-trace
    assert np.array_equal(ys["onehot"], ys["sort"])
    assert np.array_equal(ys["onehot"], ys["pallas"])


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
from repro.core.routing import owner_route, owner_route_hier
from repro.sparse.program import run_program
from repro.sparse.jax_apps import BFS, HISTOGRAM

IMPLS = ('onehot', 'sort', 'pallas')
results = []

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
    streams = {}
    for impl in IMPLS:
        def k(d_b, v_b, impl=impl):
            valid = d_b >= 0
            d_c = jnp.maximum(d_b, 0)
            rs, rv, nd = owner_route(v_b, d_c // n_dev, d_c % n_dev,
                                     valid, n_dev, cap, 'data', impl=impl)
            return rs, rv, jax.lax.psum(nd, 'data')
        f = jax.jit(shard_map_unchecked(k, mesh=mesh,
                                        in_specs=(P('data'), P('data')),
                                        out_specs=(P('data'), P('data'),
                                                   P())))
        rs, rv, nd = f(jnp.asarray(dest), jnp.asarray(vals))
        streams[impl] = (np.asarray(rs), np.asarray(rv), int(nd))
    ref = streams['onehot']
    ok = all(np.array_equal(ref[0], s[0]) and np.array_equal(ref[1], s[1])
             and ref[2] == s[2] for s in streams.values())
    results.append({'case': f'flat n_dev={n_dev}', 'identical': ok,
                    'drops': ref[2]})

# --- pod/portal: app-level states + per-round stats, tight caps ---------
from repro.sparse.datasets import rmat
g = rmat(7, edge_factor=4, seed=5)
for shape, axes in [((2, 2), ('pod', 'data')), ((2, 4), ('pod', 'data'))]:
    mesh = make_mesh(shape, axes)
    outs = {}
    for impl in IMPLS:
        (d,), stats = run_program(BFS, g, mesh, axis='data',
                                  pod_axis='pod', capacity_factor=0.5,
                                  params={'root': 0}, route_impl=impl)
        outs[impl] = (d, stats.messages.tolist(), stats.drops.tolist())
    ref = outs['onehot']
    ok = all(np.array_equal(ref[0], o[0]) and ref[1] == o[1]
             and ref[2] == o[2] for o in outs.values())
    results.append({'case': f'hier {shape}', 'identical': ok,
                    'drops': int(sum(ref[2]))})

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
