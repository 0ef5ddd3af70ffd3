"""Wall-clock benchmark of the routing hot path — the repo's first
wall-clock perf trajectory artifact.

``python -m benchmarks.route_bench [--quick] [--out BENCH_route.json]``
emits schema ``dcra-route-bench/v2`` with two kinds of wall-clock cells:

* **op-level** ``cells`` — one owner-route-shaped ``bucket()`` round
  (rank + capacity test + slot scatter, payload + one metadata column)
  per ``route_impl`` over an N x S grid, with ``speedup_vs_onehot`` per
  impl — the machine-portable number the CI gate
  (:mod:`repro.dse.route_compare`) tracks, since absolute ms do not
  transfer across runners;
* **round-level** ``round_cells`` — what users actually pay per
  iteration: a jitted multi-round min-relay loop (payload gather ->
  admission -> receive-reduce -> frontier update, the per-shard work of
  one ``run_program`` round between collectives), timed in BOTH round
  shapes per impl: ``lockstep`` (``bucket`` + ``reduce_received``, the
  classic two-pass round) vs ``pipelined`` (``local_route_reduce``, the
  one-device fold of the receive-reduce into admission). The bench
  itself asserts the two shapes are bit-identical (final state AND
  per-round drop streams) before timing,
  and ``round_speedup`` (lockstep ms / pipelined ms per impl) is gated
  by :mod:`repro.dse.route_compare` like the op-level ratios.

``pallas_lowering`` records what the "pallas" impl actually ran:
``"mosaic"`` on TPU, ``"xla"`` elsewhere (the interpreter-free tile-scan
rendering of the same algorithm — the deployed fast path; the Pallas
interpreter is never benchmarked).

The committed BENCH_route.json at the repo root is the quick-grid
baseline the bench-smoke CI job compares against.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import numpy as np


QUICK_GRID = [(4096, 8), (4096, 64), (16384, 16), (65536, 8), (65536, 64),
              (131072, 128)]
FULL_GRID = QUICK_GRID + [(262144, 64), (262144, 256)]
# Round-level cells are ~ROUNDS x the op cost, so use a smaller grid that
# still ends on the headline cell the acceptance gate tracks.
ROUND_QUICK_GRID = [(16384, 16), (65536, 64), (131072, 128)]
ROUND_FULL_GRID = ROUND_QUICK_GRID + [(262144, 256)]
ROUNDS = 6
IMPLS = ("onehot", "sort", "pallas")
MODES = ("lockstep", "pipelined")
SCHEMA = "dcra-route-bench/v2"


def _bench_cell(n: int, s: int, reps: int) -> Dict:
    import jax
    import jax.numpy as jnp
    from repro.core.queues import round8
    from repro.core.routing import bucket

    cap = round8(2 * n // max(s, 1))
    rng = np.random.default_rng(n + s)
    dest = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.9)
    vals = jnp.asarray(rng.random((n, 1)), jnp.float32)
    slot_ids = jnp.asarray(rng.integers(0, n, n), jnp.int32)

    fns = {}
    outs = {}
    est = []
    for impl in IMPLS:
        f = jax.jit(lambda v, d, va, sl, impl=impl: bucket(
            v, d, va, [sl], s, cap, impl=impl))
        outs[impl] = f(vals, dest, valid, slot_ids)    # compile
        jax.block_until_ready(outs[impl])
        t0 = time.perf_counter()                       # warm + estimate
        jax.block_until_ready(f(vals, dest, valid, slot_ids))
        est.append(time.perf_counter() - t0)
        fns[impl] = f
    # Sub-ms cells need many samples for a stable median — scale reps so
    # every impl accumulates >= ~150 ms of measurement (capped), and
    # interleave the impls per rep so machine-load drift hits all three
    # equally instead of biasing whichever ran last.
    reps = max(reps, min(100, int(0.15 / max(min(est), 1e-5)) + 1))
    times: Dict[str, List[float]] = {impl: [] for impl in IMPLS}
    for _ in range(reps):
        for impl in IMPLS:
            t0 = time.perf_counter()
            jax.block_until_ready(fns[impl](vals, dest, valid, slot_ids))
            times[impl].append(time.perf_counter() - t0)
    ms = {impl: float(np.median(times[impl]) * 1e3) for impl in IMPLS}
    # the bench is only meaningful if the impls agree — assert it here
    ref = outs["onehot"]
    for impl in ("sort", "pallas"):
        got = outs[impl]
        assert jax.numpy.array_equal(ref[0], got[0]), (n, s, impl)
        assert int(ref[3]) == int(got[3]), (n, s, impl)
    return {"n": n, "s": s, "cap": cap, "ms": ms,
            "speedup_vs_onehot": {i: ms["onehot"] / ms[i] for i in IMPLS}}


def _bench_round_cell(n: int, s: int, reps: int) -> Dict:
    """Time ROUNDS iterations of a min-relay round in both round shapes.

    The loop body is the per-shard work of one ``run_program`` round
    between collectives: gather payloads from the frontier, admit into
    capacity-bounded buckets, receive-reduce into the state vector, and
    recompute the frontier from what improved. ``lockstep`` renders it as
    the classic two-pass ``bucket`` -> ``reduce_received``; ``pipelined``
    as the fused ``local_route_reduce`` fold (what a one-device launch
    runs in either round mode). Both are asserted bit-identical — same
    final state, same per-round drop stream — before
    any timing, so the speedup column can never hide a semantic change.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.queues import round8
    from repro.core.routing import bucket, local_route_reduce, reduce_received

    cap = round8(2 * n // max(s, 1))
    n_local = max(n // 4, s)
    rng = np.random.default_rng(n + s + 1)
    src = jnp.asarray(rng.integers(0, n_local, n), jnp.int32)
    dest = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    slot_ids = jnp.asarray(rng.integers(0, n_local, n), jnp.int32)
    w = jnp.asarray(rng.random(n) + 0.05, jnp.float32)
    state0 = jnp.full((n_local,), jnp.inf, jnp.float32).at[0].set(0.0)
    frontier0 = jnp.isfinite(state0)

    def step(state, frontier, impl, mode):
        active = frontier[src]
        vals = state[src] + w
        if mode == "lockstep":
            xb, (slot_b,), _, nd = bucket(
                vals[:, None], dest, active, [slot_ids], s, cap, impl=impl)
            upd = reduce_received(slot_b, xb[:, 0], n_local, "min")
        else:
            upd, nd = local_route_reduce(
                vals, slot_ids, dest, active, s, cap, n_local, "min",
                impl=impl)
        frontier2 = upd < state
        return jnp.minimum(state, upd), frontier2, nd

    def run(impl, mode):
        def body(_, carry):
            state, frontier, drops, r = carry
            state, frontier, nd = step(state, frontier, impl, mode)
            return state, frontier, drops.at[r].set(nd), r + 1
        init = (state0, frontier0, jnp.zeros((ROUNDS,), jnp.int32),
                jnp.int32(0))
        state, _, drops, _ = jax.lax.fori_loop(0, ROUNDS, body, init)
        return state, drops

    fns = {}
    outs = {}
    est = []
    for impl in IMPLS:
        for mode in MODES:
            f = jax.jit(lambda impl=impl, mode=mode: run(impl, mode))
            outs[impl, mode] = jax.block_until_ready(f())   # compile
            t0 = time.perf_counter()                        # warm + estimate
            jax.block_until_ready(f())
            est.append(time.perf_counter() - t0)
            fns[impl, mode] = f
    # bit-identity across shapes AND impls before any timing
    ref_state, ref_drops = outs["onehot", "lockstep"]
    for key, (got_state, got_drops) in outs.items():
        assert jax.numpy.array_equal(ref_state, got_state), (n, s, key)
        assert jax.numpy.array_equal(ref_drops, got_drops), (n, s, key)
    reps = max(reps, min(50, int(0.15 / max(min(est), 1e-5)) + 1))
    times: Dict = {key: [] for key in fns}
    for _ in range(reps):
        for key, f in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            times[key].append(time.perf_counter() - t0)
    ms = {mode: {impl: float(np.median(times[impl, mode]) * 1e3)
                 for impl in IMPLS} for mode in MODES}
    return {"n": n, "s": s, "cap": cap, "rounds": ROUNDS, "round_ms": ms,
            "round_speedup": {i: ms["lockstep"][i] / ms["pipelined"][i]
                              for i in IMPLS}}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI grid (the committed baseline's grid)")
    ap.add_argument("--out", default="BENCH_route.json")
    ap.add_argument("--reps", type=int, default=0,
                    help="timing reps per impl (0 = 7 quick / 9 full)")
    args = ap.parse_args(argv)
    import jax

    grid = QUICK_GRID if args.quick else FULL_GRID
    round_grid = ROUND_QUICK_GRID if args.quick else ROUND_FULL_GRID
    reps = args.reps or (7 if args.quick else 9)
    cells: List[Dict] = []
    for n, s in grid:
        cell = _bench_cell(n, s, reps)
        cells.append(cell)
        sp = cell["speedup_vs_onehot"]
        print(f"route_bench,N={n},S={s},cap={cell['cap']},"
              f"onehot={cell['ms']['onehot']:.3f}ms,"
              f"sort={sp['sort']:.2f}x,pallas={sp['pallas']:.2f}x",
              flush=True)
    round_cells: List[Dict] = []
    for n, s in round_grid:
        cell = _bench_round_cell(n, s, reps)
        round_cells.append(cell)
        sp = cell["round_speedup"]
        print(f"round_bench,N={n},S={s},cap={cell['cap']},"
              f"rounds={cell['rounds']},"
              f"lockstep={cell['round_ms']['lockstep']['pallas']:.3f}ms,"
              f"pipelined:onehot={sp['onehot']:.2f}x,"
              f"sort={sp['sort']:.2f}x,pallas={sp['pallas']:.2f}x",
              flush=True)
    bench = {
        "schema": SCHEMA,
        "backend": jax.default_backend(),
        "pallas_lowering": ("mosaic" if jax.default_backend() == "tpu"
                            else "xla"),
        "quick": bool(args.quick),
        "impls": list(IMPLS),
        "cells": cells,
        "round_cells": round_cells,
    }
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {args.out} ({len(cells)} cells, "
          f"{len(round_cells)} round cells)")


if __name__ == "__main__":
    main()
