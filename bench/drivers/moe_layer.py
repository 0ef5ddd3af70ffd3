"""Forward steps of one MoE expert layer, dispatched back to back.

Traffic parameters (``bench/traffic/<name>.json``):

* ``batch``, ``seq``: the tokens of one step, [batch, seq, hidden] bfloat16;
* ``distinct_batches``: how many different token batches are made on the
  device from the seed and cycled;
* ``in_flight``: at most this many steps dispatched and not yet finished;
* ``sample_laps``: each batch's output is compared from one step drawn from
  the seed among its first ``sample_laps`` passes through the window;
* ``routing_tie``: tokens whose k-th and (k+1)-th reference router logits
  lie closer than this have two right answers and are not compared;
* ``trace_seconds``: the window of a traced run, where shorter;
* ``limits``: the limit of each number compared.

The weights are made by ``bench/ref/moe.py`` from the seed in one jitted
call on the device (router float32, experts bfloat16), and the layer is the
program's ``moe_dcra`` on a (1, 1, 1) ``("data", "expert", "tp")`` mesh,
traced with float32 products at full precision so that the router is the
float32 router the configuration states. After the window every sampled
output is compared with the plain float32 reference, token by token.
"""
from __future__ import annotations

import collections
import dataclasses
import sys
import time

import numpy as np

BUSY_SPANS = ()


def _arch(cfg: dict):
    """The program's configuration object at the widths of ``cfg``."""
    from repro.configs import get_config
    arch = get_config(cfg["program_config"])
    moe = dataclasses.replace(
        arch.moe, num_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], d_expert=cfg["intermediate_size"],
        capacity_factor=cfg["capacity_factor"])
    return dataclasses.replace(arch, d_model=cfg["hidden_size"], moe=moe)


def run(run) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core import dispatch
    from repro.core.compat import make_mesh
    from bench.ref import moe as ref
    cfg, tr = run.cfg, run.traffic
    d, n_exp, k = cfg["hidden_size"], cfg["num_experts"], cfg["num_experts_per_tok"]
    arch = _arch(cfg)
    t = time.perf_counter()
    seed32 = int(np.random.SeedSequence(run.seed).generate_state(1)[0])
    kw, kx = jax.random.split(jax.random.key(seed32))
    params = jax.jit(ref.init_params, static_argnums=(1, 2, 3))(
        kw, d, n_exp, cfg["intermediate_size"])
    nb, shape = tr["distinct_batches"], (tr["batch"], tr["seq"], d)
    xs = jax.jit(lambda key: [jax.random.normal(kb, shape, jnp.bfloat16)
                              for kb in jax.random.split(key, nb)])(kx)

    mesh = make_mesh((1, 1, 1), ("data", "expert", "tp"),
                     devices=run.devices[:1])
    info = dispatch.MeshInfo(mesh, pod_axis=None)

    def layer(p, x):
        with jax.default_matmul_precision("highest"):
            return dispatch.moe_dcra(p, x, arch, info)[0]

    step = jax.jit(layer)
    jax.block_until_ready((params, xs))
    t_data = time.perf_counter()
    step(params, xs[0]).block_until_ready()   # compiles, or loads the cache
    print(f"bench: set-up: start {t - run.t_start:.3f} s, weights and tokens "
          f"{t_data - t:.3f} s, warm-up step "
          f"{time.perf_counter() - t_data:.3f} s", file=sys.stderr)

    rng = np.random.default_rng(run.seed)
    sampled = {int(lap) * nb + b: b
               for b, lap in enumerate(rng.integers(0, tr["sample_laps"], nb))}
    kept, latest = {}, {}
    inflight = collections.deque()
    n = 0
    with run.window():
        t0 = time.perf_counter()
        while True:
            b = n % nb
            with run.span("bench.step"):
                out = step(params, xs[b])
            latest[b] = out
            if n in sampled:
                kept[b] = out
            inflight.append(out)
            if len(inflight) > tr["in_flight"]:
                inflight.popleft().block_until_ready()
            n += 1
            if time.perf_counter() - t0 >= run.window_seconds:
                break
        with run.span("bench.drain"):
            out.block_until_ready()
        t1 = time.perf_counter()
    tokens = n * tr["batch"] * tr["seq"]
    peak = run.memory_peak()
    outs = {b: kept.get(b, latest.get(b)) for b in range(nb) if b in latest}
    del inflight, latest, kept, out, step

    errs, ties, compared = [], 0, 0
    for b, got in outs.items():
        want, margin = ref.forward_jit(params, xs[b], top_k=k)
        err = np.asarray(ref.token_rel_err(got, want))
        sure = np.asarray(margin) >= tr["routing_tie"]
        ties += int((~sure).sum())
        compared += int(sure.sum())
        errs.append(float(err[sure].max()))
    limit = tr["limits"]["token_rel_err_max"]
    print(f"bench: compared {compared} tokens of {len(outs)} steps, "
          f"{ties} left out as routing ties", file=sys.stderr)
    return {"attempted": n, "failed": sum(e > limit for e in errs),
            "end_to_end": {"moe_tokens_per_s": tokens / (t1 - t0)},
            "memory_peak_bytes": peak,
            "checks": {"token_rel_err_max": (max(errs), limit)},
            "record": {"tokens_per_s": tokens / (t1 - t0),
                       "shape": {"d_model": d, "n_experts": n_exp,
                                 "top_k": k,
                                 "d_expert": cfg["intermediate_size"]}}}
