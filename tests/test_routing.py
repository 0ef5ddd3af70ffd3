"""Property tests for the shared owner-routed NoC collective layer
(:mod:`repro.core.routing`).

Part A — in-process properties of the pure bucketing primitives (hypothesis
or its seeded-examples shim).

Part B — the distributed round under shard_map on 1/2/4/8 host devices
(subprocess so XLA_FLAGS doesn't leak): random dest/vals/capacity, ops
add/min; the routed result must equal a numpy oracle applying the same
first-``cap``-per-(source shard, owner) keep rule, and the drop count must
equal the analytic IQ-overflow count computed by ``TaskEngine.route`` for
the same task stream.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core.routing import (bucket, pack_wire, positions_by_dest,
                                round8, unpack_wire)


# ---------------------------------------------------------------------------
# Part A: bucketing primitives
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n_buckets=st.sampled_from([1, 3, 8]))
def test_positions_by_dest_is_stable_cumcount(seed, n_buckets):
    rng = np.random.default_rng(seed)
    n = 128
    dest = rng.integers(0, n_buckets, n)
    valid = rng.random(n) < 0.8
    pos = np.asarray(positions_by_dest(jnp.asarray(dest),
                                       jnp.asarray(valid), n_buckets))
    counts = np.zeros(n_buckets, np.int64)
    for i in range(n):
        if valid[i]:
            assert pos[i] == counts[dest[i]]
            counts[dest[i]] += 1


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), cap=st.sampled_from([8, 16, 32]),
       n_buckets=st.sampled_from([2, 4, 8]))
def test_bucket_drop_count_matches_overflow(seed, cap, n_buckets):
    rng = np.random.default_rng(seed)
    n = 256
    dest = rng.integers(0, n_buckets, n)
    valid = rng.random(n) < 0.9
    vals = rng.integers(0, 100, n).astype(np.float32)
    xb, (got_vals,), task_slot, n_drop = bucket(
        jnp.asarray(vals)[:, None], jnp.asarray(dest), jnp.asarray(valid),
        [jnp.asarray(vals).astype(jnp.int32)], n_buckets, cap)
    per_bucket = np.bincount(dest[valid], minlength=n_buckets)
    want_drop = int(np.maximum(per_bucket - cap, 0).sum())
    assert int(n_drop) == want_drop
    # kept tasks land in their own slot, dropped tasks get slot -1
    slots = np.asarray(task_slot)
    assert int((slots >= 0).sum()) == int(valid.sum()) - want_drop
    kept = slots >= 0
    assert np.array_equal(np.asarray(xb)[slots[kept], 0], vals[kept])


@settings(max_examples=10, deadline=None)
@given(v=st.integers(0, 10**6))
def test_round8(v):
    r = round8(v)
    assert r % 8 == 0 and r >= max(v, 8) and r - v < 8 or v < 8


# ---------------------------------------------------------------------------
# Part B: the distributed round on 1/2/4/8 devices
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import numpy as np
import jax
from repro.core import EngineConfig, QueueConfig, TaskEngine, TileGrid
from repro.core.compat import make_mesh
from repro.sparse.jax_apps import dcra_scatter, from_owner_layout
from repro.sparse.options import LaunchOptions

def oracle(dest, vals, n, n_dev, cap, op):
    '''First-cap-per-(source shard, owner) keep rule + reduction.'''
    e_local = len(dest) // n_dev
    y = np.zeros(n) if op == 'add' else np.full(n, np.inf)
    drops = 0
    for d in range(n_dev):
        counts = np.zeros(n_dev, np.int64)
        for i in range(d * e_local, (d + 1) * e_local):
            if dest[i] < 0:
                continue
            o = dest[i] % n_dev
            if counts[o] < cap:
                counts[o] += 1
                if op == 'add':
                    y[dest[i]] += vals[i]
                else:
                    y[dest[i]] = min(y[dest[i]], vals[i])
            else:
                drops += 1
    return y, drops

cases = []
for n_dev in (1, 2, 4, 8):
    mesh = make_mesh((n_dev,), ('data',))
    for op in ('add', 'min'):
        for seed in (0, 1, 2):
            rng = np.random.default_rng(seed * 31 + n_dev * 7 +
                                        (op == 'min'))
            n = int(rng.integers(16, 200))
            e_local = int(rng.integers(4, 80))
            E = e_local * n_dev
            dest = rng.integers(0, n, E)
            dest[rng.random(E) < 0.1] = -1        # padding / no-task
            vals = rng.integers(0, 100, E).astype(np.float32)
            cf = float(rng.choice([0.25, 1.0, 4.0]))  # tight queues DO drop
            cap = max(8, -(-int(e_local * cf / n_dev) // 8) * 8)
            y_sh, dropped = dcra_scatter(
                jax.numpy.asarray(dest, jax.numpy.int32),
                jax.numpy.asarray(vals), n, mesh, op=op,
                options=LaunchOptions(capacity_factor=cf))
            y = np.asarray(from_owner_layout(y_sh, n, n_dev), np.float64)
            want, want_drops = oracle(dest, vals, n, n_dev, cap, op)
            # analytic twin: same stream through TaskEngine.route, the
            # capacity flowing through QueueConfig (the only IQ source)
            engine = TaskEngine(EngineConfig(
                grid=TileGrid(1, n_dev),
                queues=QueueConfig(default_iq=cap)), n)
            valid = dest >= 0
            shard_of = np.repeat(np.arange(n_dev), e_local)
            rs = engine.route('T3', src_idx=shard_of[valid],
                              dst_idx=dest[valid])
            cases.append({
                'desc': f'n_dev={n_dev} op={op} seed={seed} cf={cf}',
                'max_err': float(np.max(np.abs(np.where(
                    np.isfinite(want), y - want,
                    (~np.isfinite(y)).astype(float) - 1)))),
                'drops': int(dropped),
                'oracle_drops': int(want_drops),
                'engine_drops': int(rs.drops),
            })
print('RESULT ' + json.dumps(cases))
"""


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_covers_all_device_counts(cases):
    assert len(cases) == 4 * 2 * 3


def test_routed_result_matches_numpy_oracle(cases):
    bad = [c for c in cases if c["max_err"] > 1e-5]
    assert not bad, bad


def test_drop_count_matches_oracle_and_task_engine(cases):
    bad = [c for c in cases
           if not (c["drops"] == c["oracle_drops"] == c["engine_drops"])]
    assert not bad, bad


def test_some_case_actually_dropped(cases):
    """The grid must exercise the overflow path, not just the happy path."""
    assert any(c["drops"] > 0 for c in cases)


# ---------------------------------------------------------------------------
# Part C: fused-payload wire packing (what fused_all_to_all puts on the NoC)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), d=st.integers(1, 9),
       n_int=st.integers(0, 3),
       dtype=st.sampled_from(["bfloat16", "float16"]))
def test_half_width_packing_round_trips_exactly(seed, d, n_int, dtype):
    """bf16/f16 payloads with any D (odd included) round-trip bitwise and
    ride two per 32-bit lane: the wire never inflates beyond
    ceil(D/2) + n_int columns."""
    rng = np.random.default_rng(seed)
    n = 16
    # raw bit patterns (not just round numbers): bitcast must be exact
    vals = jnp.asarray(rng.random((n, d)) * 100 - 50).astype(dtype)
    ints = [jnp.asarray(rng.integers(-2**31, 2**31 - 1, n), jnp.int32)
            for _ in range(n_int)]
    packed, meta = pack_wire(vals, ints)
    assert packed.dtype == jnp.int32
    assert packed.shape == (n, -(-d // 2) + n_int)      # never inflates
    v_out, ints_out = unpack_wire(packed, meta)
    assert v_out.dtype == vals.dtype
    assert jnp.array_equal(
        jax.lax.bitcast_convert_type(v_out, jnp.uint16),
        jax.lax.bitcast_convert_type(vals, jnp.uint16))
    for a, b in zip(ints, ints_out):
        assert jnp.array_equal(a, b)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), d=st.integers(1, 5))
def test_f32_and_1d_packing_round_trip(seed, d):
    rng = np.random.default_rng(seed)
    n = 8
    vals2 = jnp.asarray(rng.random((n, d)), jnp.float32)
    ints = [jnp.asarray(rng.integers(0, 100, n), jnp.int32)]
    packed, meta = pack_wire(vals2, ints)
    assert packed.shape == (n, d + 1)
    v_out, (i_out,) = unpack_wire(packed, meta)
    assert jnp.array_equal(v_out, vals2) and jnp.array_equal(i_out, ints[0])
    vals1 = jnp.asarray(rng.random(n), jnp.float32)      # [N] squeeze path
    packed, meta = pack_wire(vals1, [])
    v_out, empty = unpack_wire(packed, meta)
    assert v_out.shape == (n,) and jnp.array_equal(v_out, vals1)
    assert empty == []
