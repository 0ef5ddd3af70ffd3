"""Mixture-of-Experts layer.

Two dispatch implementations (selected by ``MoEConfig.dispatch_impl``):

* ``einsum`` — dense dispatch/combine masks over token groups
  (Mesh-TensorFlow / GShard style). XLA SPMD partitions the einsums; this is
  the *flat-NoC baseline* in DCRA terms.
* ``dcra``  — the paper's technique: owner-routed task dispatch with bounded
  queues and a hierarchical (tile-NoC / die-NoC) all-to-all, implemented with
  ``shard_map`` in :mod:`repro.core.dispatch`. Falls back to ``einsum`` when
  no mesh is active (single-device smoke tests still exercise it via a
  trivial mesh).

Expert capacity == DCRA input-queue size: tokens beyond capacity are dropped
(counted) exactly like NoC queue overflow; the residual connection carries
them through — the standard capacity-factor semantics.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, MoEConfig
from ..core.dispatch import gate, shared_expert
from .common import dense_init, shard, swiglu

GROUP_SIZE = 1024  # tokens per dispatch group (DCRA: per-tile task batch)


def init_moe(key, cfg: ArchConfig):
    """Router [D, E], experts ``wg``/``wu`` [E, D, F] and ``wd`` [E, F, D];
    a zero ``router_bias`` [E] for sigmoid scoring, and the shared experts'
    ``shared_wg``/``shared_wu`` [D, Fs], ``shared_wd`` [Fs, D] with
    ``Fs = n_shared * d_shared``."""
    mc = cfg.moe
    assert mc is not None
    d, e, f = cfg.d_model, mc.num_experts, mc.d_expert
    ks = jax.random.split(key, 4)
    params = {
        "router": dense_init(ks[0], d, (e,), scale=0.1),
        "wg": _expert_init(ks[1], e, d, f),
        "wu": _expert_init(ks[2], e, d, f),
        "wd": _expert_init(ks[3], e, f, d),
    }
    if mc.scoring == "sigmoid":
        params["router_bias"] = jnp.zeros((e,), jnp.float32)
    if mc.n_shared:
        fs = mc.n_shared * mc.d_shared
        kg, ku, kd = jax.random.split(jax.random.fold_in(key, 1), 3)
        params.update(shared_wg=dense_init(kg, d, (fs,)),
                      shared_wu=dense_init(ku, d, (fs,)),
                      shared_wd=dense_init(kd, fs, (d,)))
    return params


def _expert_init(key, e, din, dout):
    return jax.random.normal(key, (e, din, dout)) * (din ** -0.5)


def route(params, x, mc: MoEConfig):
    """x [..., D] -> (gates [..., K], expert ids [..., K], probs [..., E]):
    the float32 router and :func:`repro.core.dispatch.gate`."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    return gate(logits, mc, params.get("router_bias"))


def capacity(group_tokens: int, mc: MoEConfig) -> int:
    c = int(group_tokens * mc.top_k * mc.capacity_factor / mc.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU lane alignment


def moe_einsum(params, x, cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    """Dense-mask dispatch. x [B, S, D] -> (out [B,S,D], aux loss []).

    It computes the whole layer: weights of a share of the experts (as
    ``moe_dcra`` takes with ``MeshInfo.expert_share``) raise."""
    mc = cfg.moe
    if params["wg"].shape[0] != mc.num_experts:
        raise ValueError(
            f"moe_einsum needs all {mc.num_experts} experts, got the "
            f"weights of {params['wg'].shape[0]}")
    B, S, D = x.shape
    T = B * S
    g_size = min(GROUP_SIZE, T)
    G = T // g_size
    xg = x.reshape(G, g_size, D)
    xg = shard(xg, "act_group", None, "act_embed")

    gates, eids, probs = route(params, xg, mc)              # [G,T,K],[G,T,E]
    onehot = jax.nn.one_hot(eids, mc.num_experts, dtype=jnp.float32)
    C = capacity(g_size, mc)

    # queue position of each (token, k) task within its expert queue
    flat = onehot.reshape(G, g_size * mc.top_k, mc.num_experts)
    pos = jnp.cumsum(flat, axis=1) * flat - flat            # 0-based [G,TK,E]
    keep = (pos < C).astype(jnp.float32) * flat             # drop = IQ overflow
    pos_k = pos.reshape(G, g_size, mc.top_k, mc.num_experts).astype(jnp.int32)
    keep_k = keep.reshape(G, g_size, mc.top_k, mc.num_experts)
    pos_oh = jax.nn.one_hot(pos_k, C, dtype=jnp.float32) * keep_k[..., None]
    # dispatch/combine [G, T, E, C] (k summed; a token goes to k distinct experts)
    dispatch = pos_oh.sum(2)
    combine = (pos_oh * gates[..., None, None]).sum(2)

    xe = jnp.einsum("gtec,gtd->gecd", dispatch.astype(x.dtype), xg)
    xe = shard(xe, "act_group", "act_expert", None, "act_embed")
    h = swiglu(jnp.einsum("gecd,edf->gecf", xe, params["wg"].astype(x.dtype)),
               jnp.einsum("gecd,edf->gecf", xe, params["wu"].astype(x.dtype)))
    ye = jnp.einsum("gecf,efd->gecd", h, params["wd"].astype(x.dtype))
    ye = shard(ye, "act_group", "act_expert", None, "act_embed")
    out = jnp.einsum("gecd,gtec->gtd", ye, combine.astype(x.dtype))

    aux = load_balance_loss(probs, onehot)
    out = out.reshape(B, S, D)
    if mc.n_shared:
        out = out + shared_expert(params, x)
    return out, aux


def load_balance_loss(probs, onehot) -> jax.Array:
    """Switch-style aux loss: E * sum_e(frac_tokens_e * mean_prob_e)."""
    E = probs.shape[-1]
    frac = onehot.sum(2).mean(axis=(0, 1))      # [E] fraction routed (pre-drop)
    mp = probs.mean(axis=(0, 1))                # [E]
    return E * jnp.sum(frac * mp)


def moe_block(params, x, cfg: ArchConfig,
              mesh_info: Optional[object] = None) -> Tuple[jax.Array, jax.Array]:
    mc = cfg.moe
    assert mc is not None
    if mc.dispatch_impl == "dcra" and mesh_info is not None:
        from ..core.dispatch import moe_dcra
        return moe_dcra(params, x, cfg, mesh_info)
    return moe_einsum(params, x, cfg)
