"""DCRA MoE dispatch vs the einsum oracle on a multi-device (fake) mesh.

Runs in a subprocess so XLA_FLAGS device-count doesn't leak into other
tests (smoke tests must see 1 device, per the dry-run spec).
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json, dataclasses
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.compat import make_mesh, set_mesh
from repro.core.dispatch import MeshInfo, moe_dcra
from repro.models.moe import init_moe, moe_einsum

cfg = get_config('olmoe-1b-7b').reduced()
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                       capacity_factor=8.0))
params = init_moe(jax.random.key(0), cfg)
x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model))
out_e, aux_e = moe_einsum(params, x, cfg)
cfg8 = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                        num_experts=8,
                                                        capacity_factor=8.0))
params8 = init_moe(jax.random.key(2), cfg8)
out_e8, _ = moe_einsum(params8, x, cfg8)

res = {}
mesh = make_mesh((2, 2, 2), ('data', 'expert', 'tp'))
info = MeshInfo(mesh, pod_axis=None)
with set_mesh(mesh):
    out_d, _ = jax.jit(lambda p, x: moe_dcra(p, x, cfg, info))(params, x)
res['single_pod_fused'] = float(jnp.max(jnp.abs(out_d - out_e)))

info_tp = MeshInfo(mesh, pod_axis=None, fuse_tp=False)
with set_mesh(mesh):
    out_t, _ = jax.jit(lambda p, x: moe_dcra(p, x, cfg, info_tp))(params, x)
res['tp_ffn'] = float(jnp.max(jnp.abs(out_t - out_e)))

mesh2 = make_mesh((2, 1, 2, 2), ('pod', 'data', 'expert', 'tp'))
info2 = MeshInfo(mesh2, pod_axis='pod')
assert info2.dispatch_plan(8)[1] is True   # spans pods (hierarchical)
with set_mesh(mesh2):
    out_h, _ = jax.jit(lambda p, x: moe_dcra(p, x, cfg8, info2))(params8, x)
res['hierarchical'] = float(jnp.max(jnp.abs(out_h - out_e8)))

with set_mesh(mesh2):
    g = jax.jit(jax.grad(lambda p, x: moe_dcra(p, x, cfg8, info2)[0].sum()))(
        params8, x)
res['grads_finite'] = all(bool(jnp.isfinite(v).all())
                          for v in jax.tree.leaves(g))

# expert shares: sigmoid group-limited routing over 32 experts, a shared
# expert; two meshes each holding 16 experts add up to the whole layer
from repro.core.dispatch import shared_expert
cfgs = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, num_experts=32, top_k=4, scoring='sigmoid', n_group=8,
    topk_group=4, routed_scaling_factor=2.5, n_shared=1, d_shared=48))
routed_only = dataclasses.replace(cfgs, moe=dataclasses.replace(
    cfgs.moe, n_shared=0))
params_s = init_moe(jax.random.key(4), cfgs)
params_s['router_bias'] = 0.05 * jax.random.normal(jax.random.key(5), (32,))
with jax.default_matmul_precision('highest'):
    whole, _ = moe_einsum(params_s, x, cfgs)
    for case, m, pod in [('share_flat', mesh, None), ('share_pods', mesh2, 'pod')]:
        parts = shared_expert(params_s, x)
        for first in (0, 16):
            ps = dict(params_s, **{k: params_s[k][first:first + 16]
                                   for k in ('wg', 'wu', 'wd')})
            info_s = MeshInfo(m, pod_axis=pod, expert_share=(first, 16))
            with set_mesh(m):
                parts = parts + jax.jit(lambda p, x: moe_dcra(
                    p, x, routed_only, info_s))(ps, x)[0]
        res[case] = float(jnp.max(jnp.abs(parts - whole)))

# the return out of the buckets against a row scatter: the oracle
# rebuilds each slot's task from the task slots (as the bucket's slot
# ints are built) and scatters the rows back through it
from unittest import mock
import repro.core.dispatch as dispatch
from repro.core.routing import bucket, gather_rows, slot_scatter


def scatter_return(layer):
    task_slots, n_returns = [], [0]

    def bucket_kept(*args):
        out = bucket(*args)
        task_slots.append(out[2])
        return out

    def gather_or_scatter(table, ids):
        if not any(ids is s for s in task_slots):
            return gather_rows(table, ids)
        n_returns[0] += 1
        n = ids.shape[0]
        task = slot_scatter(jnp.arange(1, n + 1, dtype=jnp.int32),
                            jnp.maximum(ids, 0), ids >= 0, table.shape[0]) - 1
        return slot_scatter(table, jnp.maximum(task, 0), task >= 0, n)

    with mock.patch.object(dispatch, '_bucket', bucket_kept), \
            mock.patch.object(dispatch, 'gather_rows', gather_or_scatter):
        return layer(), n_returns[0]


cfg16 = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, num_experts=16, top_k=4, capacity_factor=1.0))
params16 = init_moe(jax.random.key(6), cfg16)
for case, m, info_r in [
        ('return_fused', mesh, MeshInfo(mesh, pod_axis=None)),
        ('return_tp_ffn', mesh, MeshInfo(mesh, pod_axis=None, fuse_tp=False)),
        ('return_pods', mesh2, MeshInfo(mesh2, pod_axis='pod'))]:
    def layer():
        return jax.jit(lambda p, x: moe_dcra(p, x, cfg16, info_r)[0])(
            params16, x)
    with set_mesh(m):
        new = layer()
        old, n_returns = scatter_return(layer)
    res[case] = {'max_diff': float(jnp.max(jnp.abs(new - old))),
                 'returns': n_returns}
print('RESULT ' + json.dumps(res))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_single_pod_fused_matches_einsum(results):
    assert results["single_pod_fused"] < 1e-4


def test_tp_ffn_path_matches_einsum(results):
    assert results["tp_ffn"] < 1e-4


def test_hierarchical_two_stage_matches_einsum(results):
    assert results["hierarchical"] < 1e-4


def test_gradients_flow(results):
    assert results["grads_finite"]


@pytest.mark.parametrize("case", ["share_flat", "share_pods"])
def test_expert_shares_add_up_to_the_whole_layer(results, case):
    assert results[case] < 1e-4


@pytest.mark.parametrize("case,returns", [("return_fused", 1),
                                          ("return_tp_ffn", 1),
                                          ("return_pods", 2)])
def test_bucket_return_by_gather_equals_the_row_scatter(results, case,
                                                        returns):
    """Each packaging with 16 experts (2-8 per shard) gives the layer that
    a row scatter through each slot's task gives: one return out of the
    expert buckets, and on pods one more out of the stage-2 portal
    bucket."""
    assert results[case]["returns"] == returns
    assert results[case]["max_diff"] == 0.0


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("fit", ["below", "at", "above"])
@pytest.mark.parametrize("n_buckets", [8, 64])
def test_task_slot_gather_inverts_the_bucket(n_buckets, fit, dtype):
    """Reading bucket rows through each task's slot equals scattering
    them through each slot's task, with capacity below demand (drops),
    at the fullest bucket's demand, and above it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.routing import bucket, gather_rows, slot_scatter

    rng = np.random.default_rng(n_buckets)
    n = 32 * n_buckets
    dest = rng.integers(0, n_buckets, n)
    valid = rng.random(n) < 0.9
    demand = int(np.bincount(dest[valid], minlength=n_buckets).max())
    cap = {"below": demand // 2, "at": demand, "above": demand + 3}[fit]
    _, (task_of_slot,), task_slot, n_drop = bucket(
        jnp.zeros((n, 1), jnp.int32), jnp.asarray(dest), jnp.asarray(valid),
        [jnp.arange(n, dtype=jnp.int32)], n_buckets, cap)
    assert (int(n_drop) > 0) == (fit == "below")
    rows = jax.random.normal(jax.random.key(n_buckets),
                             (n_buckets * cap, 24), jnp.dtype(dtype))
    got = gather_rows(rows, task_slot)
    want = slot_scatter(rows, jnp.maximum(task_of_slot, 0),
                        task_of_slot >= 0, n)
    assert got.dtype == want.dtype == rows.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
