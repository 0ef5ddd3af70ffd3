"""Prefill steps through a stack of expert layers that hold a share of
their experts, dispatched back to back.

A layer is ``h + moe_dcra(RMSNorm(h))``: the program's ``rms_norm`` and
its ``moe_dcra`` on a (1, 1, 1) ``("data", "expert", "tp")`` mesh told
which experts it holds (``MeshInfo.expert_share``). Tokens route over all
``router_experts``; the chip computes its held experts' part and the
shared expert. The configuration's ``num_hidden_layers`` such layers make
one step; each is its own jitted call, the later ones writing over their
input.

Traffic parameters (``bench/traffic/<name>.json``):

* ``batch``, ``seq``: the tokens of one step, [batch, seq, hidden] bfloat16;
* ``distinct_batches``: how many different token batches are made on the
  device from the seed and cycled;
* ``in_flight``: at most this many steps dispatched and not yet finished;
* ``sample_laps``: each batch is checked from one step drawn from the seed
  among its first ``sample_laps`` passes through the window;
* ``routing_tie``: tokens whose reference choice has less room than this
  (``bench/ref/moe_share.py`` ``route``'s margin, in biased sigmoid
  score) have two right answers and are not compared;
* ``ref_block``: tokens per block of the reference;
* ``trace_seconds``: the window of a traced run, where shorter;
* ``limits``: the limit of each number compared.

The weights are made by ``bench/ref/moe_share.py`` from the seed on the
device. After the window, each sampled batch is run again through the
same compiled layers one at a time, keeping every layer's input; what each
layer added (its output minus its input) is compared token by token with
the plain float32 reference fed that same input, and the last layer's
output with the timed step's. In a traced run the device self time of the
``dcra.moe.router`` and ``dcra.moe.shared`` scopes is read from the trace
(``bench/scopes.py``) into the record.
"""
from __future__ import annotations

import collections
import functools
import sys
import time

import numpy as np

BUSY_SPANS = ()


def _arch(cfg: dict):
    """The program's configuration object for one layer of ``cfg``."""
    from repro.configs.base import ArchConfig, MoEConfig
    moe = MoEConfig(
        num_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        capacity_factor=cfg["capacity_factor"], dispatch_impl="dcra",
        scoring=cfg["scoring_func"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        n_shared=cfg["n_shared_experts"],
        d_shared=cfg["moe_intermediate_size"])
    return ArchConfig(
        name=cfg["name"], family="moe", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=0, num_kv_heads=0,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], moe=moe)


def _ref_layer(cfg: dict):
    from bench.ref import moe_share as ref
    return ref.Layer(
        n_experts=cfg["router_experts"], first=cfg["first_expert_held"],
        held=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        scaling=cfg["routed_scaling_factor"], eps=cfg["rms_norm_eps"])


def run(run) -> dict:
    cfg, tr = run.cfg, run.traffic
    arch = _arch(cfg)              # a program without shares stops here
    import jax
    import jax.numpy as jnp
    from repro.core import dispatch
    from repro.core.compat import make_mesh
    from repro.models.common import rms_norm
    from bench import scopes
    from bench import trace as bench_trace
    from bench.ref import moe_share as ref
    d, n_layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    first, held = cfg["first_expert_held"], cfg["n_routed_experts"]
    mesh = make_mesh((1, 1, 1), ("data", "expert", "tp"),
                     devices=run.devices[:1])
    info = dispatch.MeshInfo(mesh, pod_axis=None,
                             expert_share=(first, held))

    t = time.perf_counter()
    seed32 = int(np.random.SeedSequence(run.seed).generate_state(1)[0])
    kw, kx = jax.random.split(jax.random.key(seed32))
    init = jax.jit(functools.partial(
        ref.init_params, d_model=d, n_experts=cfg["router_experts"],
        held=held, d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        bias_std=cfg["e_score_correction_bias_std"]))
    params = [init(k) for k in jax.random.split(kw, n_layers)]
    nb, shape = tr["distinct_batches"], (tr["batch"], tr["seq"], d)
    xs = jax.jit(lambda key: [jax.random.normal(kb, shape, jnp.bfloat16)
                              for kb in jax.random.split(key, nb)])(kx)

    def layer(p, h):
        with jax.default_matmul_precision("highest"):
            x = rms_norm(h, p["norm"], arch.norm_eps)
            return h + dispatch.moe_dcra(p, x, arch, info)[0]

    layers = [jax.jit(layer)] + [jax.jit(layer, donate_argnums=1)] * (
        n_layers - 1)

    def step(h):
        for fn, p in zip(layers, params):
            h = fn(p, h)
        return h

    jax.block_until_ready((params, xs))
    t_data = time.perf_counter()
    with scopes.capture_hlo() as texts:
        step(xs[0]).block_until_ready()   # compiles, or loads the cache
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
            while len(inflight) >= tr["in_flight"]:
                inflight.popleft().block_until_ready()
            with run.span("bench.step"):
                out = step(xs[b])
            latest[b] = out
            if n in sampled:
                kept[b] = out
            inflight.append(out)
            n += 1
            if time.perf_counter() - t0 >= run.window_seconds:
                break
        with run.span("bench.drain"):
            out.block_until_ready()
        t1 = time.perf_counter()
    tokens = n * tr["batch"] * tr["seq"]
    peak = run.memory_peak()
    timed = {b: kept.get(b, latest.get(b)) for b in range(nb) if b in latest}
    del inflight, latest, kept, out

    record = {"tokens_per_s": tokens / (t1 - t0), "steps": n,
              "layers": n_layers, "tokens_per_step": tr["batch"] * tr["seq"],
              "shape": {"d_model": d, "n_experts": cfg["router_experts"],
                        "top_k": cfg["num_experts_per_tok"],
                        "d_expert": cfg["moe_intermediate_size"],
                        "d_shared": (cfg["n_shared_experts"]
                                     * cfg["moe_intermediate_size"])},
              "expert_slots": dispatch.slot_plan(
                  arch.moe, info, tr["batch"] * tr["seq"]).expert_slots}
    if run.trace:
        scoped = scopes.read(run.trace_dir, texts)
        (win,) = bench_trace.spans(scoped.trace, bench_trace.WINDOW_SPAN)
        record["scope_s"] = scopes.scope_self_s(scoped, win.start, win.end)

    spec = _ref_layer(cfg)
    check = jax.jit(functools.partial(ref.check_layer, layer=spec,
                                      block=tr["ref_block"]))
    errs, timed_diff, held_tasks = [], [], []   # per sampled step
    ties = compared = 0
    for b, got in timed.items():
        h, step_errs = xs[b], []
        for fn, p in zip(layers, params):
            h_next = fn(p, h if fn is layers[0] else jnp.copy(h))
            err, margin, held_b = (np.asarray(a) for a in check(p, h, h_next))
            sure = margin >= tr["routing_tie"]
            ties += int((~sure).sum())
            compared += int(sure.sum())
            step_errs.append(float(err[sure].max()))
            held_tasks.append(int(held_b.sum()))
            h = h_next
        errs.append(max(step_errs))
        timed_diff.append(float(jnp.max(jnp.abs(
            h.astype(jnp.float32) - got.astype(jnp.float32)))))
    limits = tr["limits"]
    print(f"bench: compared {compared} token-layers of {len(timed)} steps, "
          f"{ties} left out as routing ties; held tasks per layer-step "
          f"{held_tasks}", file=sys.stderr)
    record["held_tasks"] = float(np.mean(held_tasks))
    record["ties"] = ties
    failed = sum(e > limits["layer_rel_err_max"]
                 or d > limits["timed_vs_layers_max"]
                 for e, d in zip(errs, timed_diff))
    return {"attempted": n, "failed": failed,
            "end_to_end": {"moe_tokens_per_s": tokens / (t1 - t0)},
            "memory_peak_bytes": peak,
            "checks": {
                "timed_vs_layers_max": (max(timed_diff),
                                        limits["timed_vs_layers_max"]),
                "layer_rel_err_max": (max(errs),
                                      limits["layer_rel_err_max"])},
            "record": record}
