"""Sigmoid group-limited routing, shared experts and expert shares in the
MoE layer (CPU, small widths).

* ``gate`` with sigmoid scoring chooses what a brute-force numpy
  reading of DeepSeek-V3's ``noaux_tc`` rule chooses: the bias moves the
  choice, never a gate value.
* ``moe_dcra`` and ``moe_einsum`` compute the same layer with sigmoid
  routing and a shared expert; ``moe_einsum`` refuses a share.
* ``slot_plan`` sizes the buckets of a share from the tasks its experts
  expect, and those are the buckets ``moe_dcra`` allocates.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import dispatch
from repro.core.compat import make_mesh
from repro.models.moe import init_moe, moe_einsum

D, E, GROUPS, TOPK_GROUP, K, HELD = 64, 32, 8, 4, 4, 8


def _cfg(**moe):
    cfg = get_config("olmoe-1b-7b").reduced()
    fields = dict(num_experts=E, top_k=K, d_expert=32, capacity_factor=8.0,
                  scoring="sigmoid", n_group=GROUPS, topk_group=TOPK_GROUP,
                  routed_scaling_factor=2.5, n_shared=1, d_shared=48)
    fields.update(moe)
    return dataclasses.replace(cfg, d_model=D, moe=dataclasses.replace(
        cfg.moe, **fields))


def _info(share=None):
    return dispatch.MeshInfo(make_mesh((1, 1, 1), ("data", "expert", "tp")),
                             pod_axis=None, expert_share=share)


def _brute_force(logits, bias, n_group, topk_group, k, scaling):
    """DeepSeek-V3's noaux_tc choice, token by token, in float64."""
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    choice = scores + bias
    per_group = choice.shape[1] // n_group
    out = []
    for s, c in zip(scores, choice):
        group_score = [np.sort(c[g * per_group:(g + 1) * per_group])[-2:].sum()
                       for g in range(n_group)]
        kept = np.argsort(group_score)[::-1][:topk_group]
        cand = [e for g in kept for e in range(g * per_group,
                                                (g + 1) * per_group)]
        chosen = sorted(cand, key=lambda e: -c[e])[:k]
        w = s[chosen] / s[chosen].sum() * scaling
        out.append(dict(zip(chosen, w)))
    return out


def test_sigmoid_group_limited_gate_matches_brute_force():
    mc = _cfg().moe
    rng = np.random.default_rng(0)
    logits = rng.normal(scale=0.5, size=(256, E)).astype(np.float32)
    bias = rng.normal(scale=0.1, size=E).astype(np.float32)
    gates, eids, probs = dispatch.gate(jnp.asarray(logits), mc,
                                       jnp.asarray(bias))
    want = _brute_force(logits, bias, GROUPS, TOPK_GROUP, K, 2.5)
    for t, w in enumerate(want):
        got = dict(zip(np.asarray(eids[t]).tolist(),
                       np.asarray(gates[t]).tolist()))
        assert set(got) == set(w)
        for e in w:
            assert got[e] == pytest.approx(w[e], rel=1e-5)
    np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.0, rtol=1e-5)
    # the bias moves the choice of some tokens ...
    no_bias = _brute_force(logits, 0 * bias, GROUPS, TOPK_GROUP, K, 2.5)
    moved = [t for t in range(len(want)) if set(want[t]) != set(no_bias[t])]
    assert len(moved) > 10
    # ... and never a gate value: with the same choice, the same gates
    same = [t for t in range(len(want)) if set(want[t]) == set(no_bias[t])]
    assert same
    for t in same:
        for e in want[t]:
            assert want[t][e] == pytest.approx(no_bias[t][e], rel=1e-9)


def test_group_limit_keeps_only_the_best_groups():
    mc = _cfg().moe
    logits = np.full((1, E), -4.0, np.float32)
    # group 0 holds the single best expert, groups 1-4 hold pairs that sum
    # higher: the limit to 4 groups leaves group 0 out
    logits[0, 0] = 3.0
    for g in range(1, 5):
        logits[0, g * 4:g * 4 + 2] = 2.0
    _, eids, _ = dispatch.gate(jnp.asarray(logits), mc)
    assert 0 not in np.asarray(eids[0]).tolist()
    plain = dataclasses.replace(mc, n_group=1, topk_group=1)
    _, eids, _ = dispatch.gate(jnp.asarray(logits), plain)
    assert 0 in np.asarray(eids[0]).tolist()


def test_softmax_gate_is_renormalised_top_k():
    mc = get_config("olmoe-1b-7b").moe
    logits = jax.random.normal(jax.random.key(0), (16, mc.num_experts))
    gates, eids, probs = dispatch.gate(logits, mc)
    want_p = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(want_p, mc.top_k)
    np.testing.assert_array_equal(np.asarray(eids), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(gates),
                               np.asarray(top / top.sum(-1, keepdims=True)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(want_p))


def test_dcra_and_einsum_agree_with_sigmoid_routing_and_shared_expert():
    cfg = _cfg()
    params = init_moe(jax.random.key(0), cfg)
    params["router_bias"] = 0.02 * jax.random.normal(jax.random.key(3), (E,))
    x = jax.random.normal(jax.random.key(1), (2, 64, D))
    with jax.default_matmul_precision("highest"):
        out_d, _ = jax.jit(lambda p, x: dispatch.moe_dcra(
            p, x, cfg, _info()))(params, x)
        out_e, _ = moe_einsum(params, x, cfg)
        shared = dispatch.shared_expert(params, x)
        routed_d, _ = dispatch.moe_dcra(
            params, x, dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, n_shared=0)), _info())
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_e),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_d - routed_d),
                               np.asarray(shared), rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(shared).max()) > 0.1


def test_moe_einsum_refuses_a_share_of_the_experts():
    cfg = _cfg()
    params = init_moe(jax.random.key(0), cfg)
    params.update({k: params[k][:HELD] for k in ("wg", "wu", "wd")})
    with pytest.raises(ValueError, match="all 32 experts"):
        moe_einsum(params, jnp.ones((1, 8, D)), cfg)


@pytest.mark.parametrize("tokens,first,capacity_factor", [
    (128, 0, 2.0), (128, 8, 2.0), (96, 24, 1.25), (64, 16, 8.0)])
def test_slot_plan_of_a_share_is_what_moe_dcra_allocates(
        tokens, first, capacity_factor, monkeypatch):
    cfg = _cfg(capacity_factor=capacity_factor)
    info = _info((first, HELD))
    params = init_moe(jax.random.key(0), cfg)
    params.update({k: params[k][first:first + HELD]
                   for k in ("wg", "wu", "wd")})
    buckets, ffn_rows = [], []
    bucket, ffn = dispatch._bucket, dispatch._expert_ffn

    def bucket_spy(x, dest, valid, aux, n_buckets, cap):
        buckets.append((dest.shape[0], n_buckets, cap))
        return bucket(x, dest, valid, aux, n_buckets, cap)

    def ffn_spy(xe, *a):
        ffn_rows.append(xe.shape[0] * xe.shape[1])
        return ffn(xe, *a)

    monkeypatch.setattr(dispatch, "_bucket", bucket_spy)
    monkeypatch.setattr(dispatch, "_expert_ffn", ffn_spy)
    jax.eval_shape(lambda p, x: dispatch.moe_dcra(p, x, cfg, info), params,
                   jnp.ones((2, tokens // 2, D)))
    plan = dispatch.slot_plan(cfg.moe, info, tokens)
    # the tasks the held experts expect: tokens x top-k x held / E
    assert plan.tasks == tokens * K * HELD // E
    assert plan.cap1 == dispatch.dispatch_queues(cfg.moe).channel_cap(
        "dispatch", plan.tasks, 1)
    # every task is ranked, only the held ones fill the stage-1 bucket
    assert buckets == [(tokens * K, 1, plan.cap1), (plan.cap1, HELD,
                                                    plan.cap_e)]
    assert ffn_rows == [plan.expert_slots] == [HELD * plan.cap_e]
    assert plan.slot_fill == plan.tasks / plan.expert_slots


def test_slot_plan_of_the_benchmark_share():
    """65,536 tokens, top-8 of 256 experts with 8 held, capacity factor 2:
    16,384 held tasks expected, a stage-1 bucket of twice that, and 8
    expert buckets of twice the bucket's share each: a quarter of the
    expert rows hold a task."""
    mc = dataclasses.replace(_cfg().moe, num_experts=256, top_k=8,
                             capacity_factor=2.0)
    plan = dispatch.slot_plan(mc, _info((0, 8)), 65536)
    assert (plan.tasks, plan.dispatch_slots, plan.cap_e,
            plan.expert_slots) == (16384, 32768, 8192, 65536)
    assert plan.slot_fill == 0.25
