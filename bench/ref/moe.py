"""Plain float32 forward of one OLMoE expert layer, kept with the benchmark.

The layer: a float32 router (softmax over the experts, top-k, the k gates
renormalised to sum to 1), then each token's k SwiGLU experts
``(silu(x Wg) * (x Wu)) Wd``, summed with their gates. No capacity and no
dropped token: every expert sees every token routed to it. It is written
from that description with ``jax.numpy`` alone, imports nothing of the
program, and runs every matrix product at ``precision="highest"``.

``fp8=True`` is the control: the expert products' operands (tokens,
weights and the SwiGLU activations) rounded to float8 e4m3 with one
amax scale per tensor, the precision below the bfloat16 the configuration
states. The router stays float32 in both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def init_params(key, d_model: int, n_experts: int, d_expert: int):
    """Seeded weights: router [D, E] float32 with std 0.1/sqrt(D); expert
    weights Wg, Wu [E, D, F] and Wd [E, F, D] with std 1/sqrt(fan-in),
    stored in bfloat16 as they are served."""
    kr, kg, ku, kd = jax.random.split(key, 4)
    router = jax.random.normal(kr, (d_model, n_experts)) * (0.1 * d_model ** -0.5)

    def expert(k, din, dout):
        w = jax.random.normal(k, (n_experts, din, dout)) * din ** -0.5
        return w.astype(jnp.bfloat16)

    return {"router": router.astype(jnp.float32),
            "wg": expert(kg, d_model, d_expert),
            "wu": expert(ku, d_model, d_expert),
            "wd": expert(kd, d_expert, d_model)}


def _fp8(a):
    """Round to float8 e4m3 under one per-tensor amax scale (448 = the
    largest e4m3 value), back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def route(router, x, top_k: int):
    """x [T, D] float32 -> (gates [T, E] with k non-zeros, margin [T]):
    ``margin`` is the gap between the k-th and (k+1)-th router logit, the
    room a token's expert choice has before rounding could change it."""
    logits = jnp.dot(x, router.astype(jnp.float32), precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    gates = jnp.zeros_like(probs).at[rows, idx].set(vals)
    top = jax.lax.top_k(logits, top_k + 1)[0]
    return gates, top[:, top_k - 1] - top[:, top_k]


def forward(params, x, top_k: int, fp8: bool = False):
    """x [..., D] -> (out [T, D] float32, margin [T])."""
    x = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    gates, margin = route(params["router"], x, top_k)
    q = _fp8 if fp8 else (lambda a: a)
    xq = q(x)

    def body(e, out):
        wg = q(params["wg"][e].astype(jnp.float32))
        wu = q(params["wu"][e].astype(jnp.float32))
        wd = q(params["wd"][e].astype(jnp.float32))
        h = (jax.nn.silu(jnp.dot(xq, wg, precision=HIGHEST))
             * jnp.dot(xq, wu, precision=HIGHEST))
        y = jnp.dot(q(h), wd, precision=HIGHEST)
        return out + gates[:, e][:, None] * y

    n_experts = params["router"].shape[1]
    out = jax.lax.fori_loop(0, n_experts, body, jnp.zeros_like(x))
    return out, margin


forward_jit = jax.jit(forward, static_argnames=("top_k", "fp8"))


def token_rel_err(got, want):
    """Per-token ||got - want|| / ||want|| over the last axis."""
    got = jnp.asarray(got, jnp.float32).reshape(want.shape)
    return (jnp.linalg.norm(got - want, axis=-1)
            / jnp.maximum(jnp.linalg.norm(want, axis=-1), 1e-30))
