"""Plain float32 forward of DeepSeek-V3's MoE layer on one chip's share of
its routed experts, kept with the benchmark.

The layer (DeepSeek-V3, arXiv:2412.19437, and its published
``config.json``), for residual-stream tokens ``h`` [T, D]:

1. RMSNorm: ``h / sqrt(mean(h**2) + eps) * norm``, rounded to bfloat16,
   the token dtype the configuration states (DeepSeek-V3's own RMSNorm
   returns its input's dtype);
2. the router over all E experts: float32 logits, sigmoid scores ``s``,
   and ``s + bias`` (``e_score_correction_bias``) to choose with. The E
   experts fall in ``n_group`` equal groups; a group's score is the sum of
   its two best biased scores, and only the ``topk_group`` best groups are
   kept. The ``top_k`` best biased scores of the kept experts are chosen;
   their gates are their unbiased scores, renormalised to sum to 1 and
   multiplied by ``routed_scaling_factor``. Experts of dropped groups are
   masked to -inf (DeepSeek-V3's code writes 0.0 there, which could pick
   one over a negative biased score);
3. the routed sum over the experts held here, ``[first, first + held)``:
   each one's SwiGLU ``(silu(x Wg) * (x Wu)) Wd`` weighted by its gate. The
   other experts' part is left out, as it lies on other chips;
4. plus the shared expert, a SwiGLU of its own over every token.

The layer adds what steps 3-4 give to ``h``. No capacity and no dropped
token. It is written from that description with ``jax.numpy`` alone,
imports nothing of the program, runs every matrix product at
``precision="highest"``, and works through the tokens in blocks so that it
fits beside the program on the chip.

``fp8=True`` is a control: the expert products' operands (tokens, weights
and the SwiGLU activations, routed and shared) rounded to float8 e4m3 with
one amax scale per tensor, the precision below the bfloat16 the
configuration states. ``group_limit=False`` chooses the plain top-k of all
E experts; ``shared=False`` leaves the shared expert out. The router stays
float32 in all.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Layer(NamedTuple):
    """The shape and routing rule of one layer."""
    n_experts: int            # the router's width
    first: int                # experts [first, first + held) are here
    held: int
    top_k: int
    n_group: int
    topk_group: int
    scaling: float            # routed_scaling_factor
    eps: float                # RMSNorm epsilon


def init_params(key, d_model: int, n_experts: int, held: int, d_expert: int,
                d_shared: int, bias_std: float):
    """Seeded weights of one layer: router [D, E] float32 with std
    0.1/sqrt(D); ``router_bias`` [E] float32 with std ``bias_std``; the
    held experts' Wg, Wu [held, D, F] and Wd [held, F, D], and the shared
    expert's [D, Fs] and [Fs, D], with std 1/sqrt(fan-in), stored in
    bfloat16 as they are served; the RMSNorm weight [D], ones, bfloat16."""
    kr, kb, kg, ku, kd, ksg, ksu, ksd = jax.random.split(key, 8)

    def w(k, shape):
        return (jax.random.normal(k, shape) * shape[-2] ** -0.5).astype(
            jnp.bfloat16)

    return {
        "router": (jax.random.normal(kr, (d_model, n_experts))
                   * (0.1 * d_model ** -0.5)).astype(jnp.float32),
        "router_bias": (jax.random.normal(kb, (n_experts,))
                        * bias_std).astype(jnp.float32),
        "wg": w(kg, (held, d_model, d_expert)),
        "wu": w(ku, (held, d_model, d_expert)),
        "wd": w(kd, (held, d_expert, d_model)),
        "shared_wg": w(ksg, (d_model, d_shared)),
        "shared_wu": w(ksu, (d_model, d_shared)),
        "shared_wd": w(ksd, (d_shared, d_model)),
        "norm": jnp.ones((d_model,), jnp.bfloat16),
    }


def _fp8(a):
    """Round to float8 e4m3 under one per-tensor amax scale (448 = the
    largest e4m3 value), back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rms_norm(h, weight, eps: float):
    """h [T, D] -> RMSNorm rounded to bfloat16's precision, in float32.

    The rounding is ``reduce_precision``, not a round trip through
    bfloat16: XLA may drop a float32 -> bfloat16 -> float32 pair as
    excess precision (the TPU compiler does), and the router would then
    see other tokens than the layer's."""
    h = h.astype(jnp.float32)
    y = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (jax.lax.reduce_precision(y, exponent_bits=8, mantissa_bits=7)
            * weight.astype(jnp.float32))


def route(router, bias, x, layer: Layer, group_limit: bool = True):
    """x [T, D] float32 -> (gates [T, E], margin [T]).

    ``gates`` holds each token's k scaled gates at its chosen experts and
    zeros elsewhere. ``margin`` is the room the choice has before rounding
    could change it: the smaller of the gap between the ``topk_group``-th
    and next group score and the gap between the k-th and (k+1)-th kept
    biased score."""
    T, E = x.shape[0], layer.n_experts
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision=HIGHEST))
    choice = scores + bias
    margin = jnp.full((T,), jnp.inf)
    if group_limit:
        groups = choice.reshape(T, layer.n_group, E // layer.n_group)
        group_score = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)
        ranked = -jnp.sort(-group_score, axis=-1)
        margin = (ranked[:, layer.topk_group - 1]
                  - ranked[:, layer.topk_group])
        kept = group_score >= ranked[:, layer.topk_group - 1:layer.topk_group]
        choice = jnp.where(jnp.repeat(kept, E // layer.n_group, axis=1),
                           choice, -jnp.inf)
    top, idx = jax.lax.top_k(choice, layer.top_k + 1)
    margin = jnp.minimum(margin, top[:, layer.top_k - 1] - top[:, layer.top_k])
    idx = idx[:, :layer.top_k]
    chosen = jnp.take_along_axis(scores, idx, axis=1)
    chosen = chosen / jnp.sum(chosen, axis=1, keepdims=True) * layer.scaling
    rows = jnp.arange(T)[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(chosen), margin


def _swiglu(x, wg, wu, wd, q):
    h = (jax.nn.silu(jnp.dot(q(x), q(wg.astype(jnp.float32)), precision=HIGHEST))
         * jnp.dot(q(x), q(wu.astype(jnp.float32)), precision=HIGHEST))
    return jnp.dot(q(h), q(wd.astype(jnp.float32)), precision=HIGHEST)


def moe(params, x, layer: Layer, fp8: bool = False, group_limit: bool = True,
        shared: bool = True):
    """The MoE part for RMSNorm-ed tokens x [T, D]: (out [T, D] float32,
    margin [T], held tasks [T]: how many of each token's k experts are
    held here)."""
    x = x.astype(jnp.float32)
    gates, margin = route(params["router"], params["router_bias"], x, layer,
                          group_limit)
    mine = gates[:, layer.first:layer.first + layer.held]
    q = _fp8 if fp8 else (lambda a: a)

    def body(e, out):
        y = _swiglu(x, params["wg"][e], params["wu"][e], params["wd"][e], q)
        return out + mine[:, e][:, None] * y

    out = jax.lax.fori_loop(0, layer.held, body, jnp.zeros_like(x))
    if shared:
        out = out + _swiglu(x, params["shared_wg"], params["shared_wu"],
                            params["shared_wd"], q)
    return out, margin, jnp.sum(mine > 0, axis=1)


def check_layer(params, h, h_next, layer: Layer, block: int):
    """Compare what a layer added to tokens h [T, D] (``h_next - h``) with
    this reference fed the same h, ``block`` tokens at a time: (per-token
    relative error [T], margin [T], held tasks [T])."""
    D = h.shape[-1]

    def one(pair):
        hb, nb = (a.astype(jnp.float32) for a in pair)
        want, margin, held = moe(params, rms_norm(hb, params["norm"],
                                                  layer.eps), layer)
        return token_rel_err(nb - hb, want), margin, held

    err, margin, held = jax.lax.map(
        one, (h.reshape(-1, block, D), h_next.reshape(-1, block, D)))
    return err.reshape(-1), margin.reshape(-1), held.reshape(-1)


def token_rel_err(got, want):
    """Per-token ||got - want|| / ||want|| over the last axis."""
    got = jnp.asarray(got, jnp.float32).reshape(want.shape)
    return (jnp.linalg.norm(got - want, axis=-1)
            / jnp.maximum(jnp.linalg.norm(want, axis=-1), 1e-30))
