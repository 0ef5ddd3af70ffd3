"""Bring-up smoke test on TPU: the main path, end to end, checked.

    python chip_smoke.py              # one chip: graph apps, serving, MoE
    python chip_smoke.py --chips 4    # a 2x2 host: the multi-chip path only

One chip drives, through the entry points a user calls:

* graph analytics — BFS, SSSP, WCC, PageRank, k-core, SpMV and histogram
  on a one-device ``Fabric`` over a Graph500 RMAT-20 graph (1M
  vertices, ~3.1e7 directed edges), each checked against the independent
  numpy oracles of ``repro.sparse.ref``: exact for the integer/min
  programs, within f32 accumulation error for PageRank and SpMV, and with
  zero queue drops;
* serving — a ``ProgramServer`` over a resident RMAT-18 graph answers 16
  BFS/SSSP requests from 4 tenants at inflight depth 2, with no re-trace
  after pre-warm and one response bit-identical to a standalone launch;
* MoE dispatch — one ``moe_dcra`` layer at OLMoE-1B-7B's published widths
  (d_model 2048, 64 experts, top-8, d_expert 1024) against the dense
  ``moe_einsum`` reference, at a capacity where neither drops a token.

``--chips 4`` runs BFS, SSSP and WCC on a flat (4,) fabric and a (2, 2)
pod/portal fabric against the oracle and against the first device alone,
and ``moe_dcra`` with its experts over 4 chips against ``moe_einsum``.

Every printed time is the first call's wall clock — host-side edge
packing, compilation and transfers included — and is not a measurement.
The last line is one JSON object naming the device. Without a TPU the
script exits non-zero and prints no result; any failed check raises.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# With the graph at Graph500 scale 22 and serving at 20, this script ran
# 2006 s on one TPU v5e, mostly first-call time (every launch re-packs its
# edges on the host, then compiles); two scales less keep it inside 1200 s.
GRAPH_SCALE = 20        # RMAT-20: WCC's f32 vertex-id labels stay exact
SERVE_SCALE = 18        # x 4 tenant columns ~ the RMAT-20 edge count
MULTI_SCALE = 20
KCORE_K = 16
HIST_ELEMENTS, HIST_BINS = 1 << 24, 4096
F32_TOL = 1e-4          # normwise relative bound for f32 accumulations
MOE_BATCH, MOE_SEQ = 8, 512
MOE_CAPACITY_FACTOR = 2.0


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg) -> None:
    """A failed check ends the run (unlike ``assert``, also under -O)."""
    if not ok:
        raise AssertionError(msg)


def require_tpu():
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform "
                 f"{devices[0].platform!r}")
    return devices


def max_err(got, want) -> float:
    """Largest |got - want|; equal entries (matching infinities) are 0."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    with np.errstate(invalid="ignore"):
        diff = np.where(got == want, 0.0, np.abs(got - want))
    return float(diff.max(initial=0.0))


def rel_err(got, want) -> float:
    return max_err(got, want) / max(float(np.abs(want).max()), 1e-30)


# ---------------------------------------------------------------------------
# graph analytics on one device
# ---------------------------------------------------------------------------

def graph_phase(scale: int, fab) -> None:
    from repro.sparse import datasets, ref
    from repro.sparse.jax_apps import (dcra_bfs, dcra_histogram, dcra_kcore,
                                       dcra_pagerank, dcra_spmv, dcra_sssp,
                                       dcra_wcc)
    t0 = time.perf_counter()
    g = datasets.rmat(scale, edge_factor=16)
    log(f"graph: RMAT-{scale} V={g.n} E={g.nnz} built on host in "
        f"{time.perf_counter() - t0:.1f}s")
    root = int(np.argmax(g.degrees()))
    x = np.random.default_rng(0).random(g.n)
    els = datasets.histogram_data(HIST_ELEMENTS, HIST_BINS)

    def single_round(out, n_tasks):
        y, drops = out
        return y, 1, n_tasks, int(drops)

    def multi_round(out):
        y, stats = out
        return y, stats.rounds, stats.total_messages, stats.total_drops

    apps = [
        ("bfs", lambda: multi_round(dcra_bfs(g, root, fab)),
         lambda: ref.bfs_ref(g, root), None),
        ("sssp", lambda: multi_round(dcra_sssp(g, root, fab)),
         lambda: ref.sssp_ref(g, root), None),
        ("wcc", lambda: multi_round(dcra_wcc(g, fab)),
         lambda: ref.wcc_ref(g), None),
        ("pagerank", lambda: multi_round(dcra_pagerank(g, fab)),
         lambda: ref.pagerank_ref(g), F32_TOL),
        ("kcore", lambda: multi_round(dcra_kcore(g, KCORE_K, fab)),
         lambda: ref.kcore_ref(g, KCORE_K), None),
        ("spmv", lambda: single_round(dcra_spmv(g, x, fab), g.nnz),
         lambda: ref.spmv_ref(g, x), F32_TOL),
        ("histogram", lambda: single_round(
            dcra_histogram(els, HIST_BINS, fab), len(els)),
         lambda: ref.histogram_ref(els, HIST_BINS), None),
    ]
    log(f"{'app':10s} {'rounds':>6s} {'messages':>11s} {'drops':>5s} "
        f"{'max_err':>9s} {'first_call_s':>12s}")
    for name, run, oracle, tol in apps:
        t0 = time.perf_counter()
        got, rounds, msgs, drops = run()
        wall = time.perf_counter() - t0
        want = oracle()
        err = max_err(got, want)
        log(f"{name:10s} {rounds:6d} {msgs:11d} {drops:5d} {err:9.2e} "
            f"{wall:12.2f}")
        check(drops == 0, f"{name}: {drops} queue drops")
        if tol is None:
            check(np.array_equal(np.asarray(got, np.float64),
                                 np.asarray(want, np.float64)),
                  f"{name}: differs from the oracle (max err {err})")
        else:
            check(rel_err(got, want) <= tol,
                  f"{name}: relative error {rel_err(got, want)} > {tol}")
    log("phase graph: ok (first_call_s = first-run wall clock incl. host "
        "packing and compile, not a measurement)")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serving_phase(scale: int, fab) -> None:
    from repro.serve import ProgramServer, Request, STATUS_OK, ServeOptions
    from repro.sparse import datasets, program, ref
    from repro.sparse.jax_apps import BFS
    g = datasets.rmat(scale, edge_factor=16, seed=2)
    server = ProgramServer(fab, {"rmat": g}, batch_width=4,
                           serve_options=ServeOptions(inflight_depth=2))
    t0 = time.perf_counter()
    server.prewarm(("bfs", "sssp"))
    warm_s = time.perf_counter() - t0
    # roots among the hubs: every query reaches the giant component
    hubs = np.argsort(g.degrees())[-64:]
    rng = np.random.default_rng(0)
    reqs = [Request(req_id=i, tenant=f"tenant{i % 4}",
                    program=("bfs", "sssp")[(i // 4) % 2], graph="rmat",
                    root=int(rng.choice(hubs)))
            for i in range(16)]
    traces0 = program.cache_stats()["kernel_traces"]
    t0 = time.perf_counter()
    responses = server.run(reqs)
    serve_s = time.perf_counter() - t0
    re_traces = program.cache_stats()["kernel_traces"] - traces0
    snap = server.stats.snapshot()
    log(f"serving: RMAT-{scale} V={g.n} E={g.nnz} x4 tenant columns, "
        f"{len(responses)} responses, launches={snap['launches']} "
        f"re_traces={re_traces} noc_drops={snap['noc_drops']} "
        f"prewarm_s={warm_s:.2f} serve_s={serve_s:.2f} (first-run wall "
        f"clock)")
    bad = [(r.req_id, r.status, r.reason) for r in responses
           if r.status != STATUS_OK]
    check(not bad, bad)
    check(re_traces == 0, f"{re_traces} re-traces after pre-warm")
    check(snap["noc_drops"] == 0, f"{snap['noc_drops']} NoC drops")
    server.stats.verify()
    first = min(responses, key=lambda r: r.req_id)
    req = reqs[first.req_id]
    (alone,), _ = program.run_program(BFS, g, fab,
                                      params={"root": req.root})
    check(np.array_equal(np.asarray(first.result), np.asarray(alone)),
          "batched response != standalone run_program")
    hops = np.where(np.isfinite(alone), alone, -1)
    check(np.array_equal(hops, ref.bfs_ref(g, req.root)),
          "standalone BFS differs from the oracle")
    log("phase serving: ok")


# ---------------------------------------------------------------------------
# MoE dispatch
# ---------------------------------------------------------------------------

def moe_phase(devices) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.compat import make_mesh
    from repro.core.dispatch import MeshInfo, moe_dcra
    from repro.models.moe import (GROUP_SIZE, capacity, init_moe,
                                  moe_einsum, route)
    cfg = get_config("olmoe-1b-7b")
    mc = dataclasses.replace(cfg.moe, capacity_factor=MOE_CAPACITY_FACTOR)
    cfg = dataclasses.replace(cfg, moe=mc)
    params = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1),
                          (MOE_BATCH, MOE_SEQ, cfg.d_model), jnp.float32)
    # the einsum reference drops nothing when no expert of any token
    # group receives more than its capacity
    _, eids, _ = route(params, x.reshape(-1, GROUP_SIZE, cfg.d_model), mc)
    load = np.stack([np.bincount(np.asarray(e).ravel(),
                                 minlength=mc.num_experts)
                     for e in eids]).max()
    cap = capacity(GROUP_SIZE, mc)
    check(load <= cap, f"einsum reference would drop: load {load} > {cap}")
    n = len(devices)
    mesh = make_mesh((1, n, 1), ("data", "expert", "tp"), devices=devices)
    info = MeshInfo(mesh, pod_axis=None)
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        out_d = np.asarray(jax.jit(
            lambda p, x: moe_dcra(p, x, cfg, info)[0])(params, x))
        dcra_s = time.perf_counter() - t0
        out_e = np.asarray(jax.jit(
            lambda p, x: moe_einsum(p, x, cfg)[0])(params, x))
    err = rel_err(out_d, out_e)
    log(f"moe: olmoe-1b-7b layer d_model={cfg.d_model} experts="
        f"{mc.num_experts} top_k={mc.top_k} d_expert={mc.d_expert} "
        f"x={list(x.shape)} f32 on {n} chip(s): max group load {load} <= "
        f"capacity {cap}, rel_err vs einsum {err:.2e}, moe_dcra first "
        f"call {dcra_s:.2f}s")
    check(np.all(np.isfinite(out_d)), "non-finite moe_dcra output")
    check(err <= F32_TOL, f"moe_dcra vs moe_einsum rel_err {err}")
    log("phase moe: ok")


# ---------------------------------------------------------------------------
# the multi-chip path (--chips 4)
# ---------------------------------------------------------------------------

def multichip_phase(scale: int, devices) -> None:
    from repro.core.fabric import Fabric
    from repro.sparse import datasets, ref
    from repro.sparse.jax_apps import dcra_bfs, dcra_sssp, dcra_wcc
    from repro.sparse.options import LaunchOptions
    g = datasets.rmat(scale, edge_factor=16)
    root = int(np.argmax(g.degrees()))
    fabrics = [("device0", Fabric.single((1,), ("data",)), None),
               ("flat(4,)", Fabric.single((4,), ("data",)), None),
               ("pod(2,2)", Fabric.single((2, 2), ("pod", "data")), "pod")]
    def on(pa):
        return LaunchOptions(pod_axis=pa)
    apps = [("bfs", lambda fab, pa: dcra_bfs(g, root, fab, options=on(pa)),
             lambda: ref.bfs_ref(g, root)),
            ("sssp", lambda fab, pa: dcra_sssp(g, root, fab, options=on(pa)),
             lambda: ref.sssp_ref(g, root)),
            ("wcc", lambda fab, pa: dcra_wcc(g, fab, options=on(pa)),
             lambda: ref.wcc_ref(g))]
    log(f"multichip: RMAT-{scale} V={g.n} E={g.nnz}")
    log(f"{'app':6s} {'fabric':9s} {'rounds':>6s} {'messages':>11s} "
        f"{'drops':>5s} {'max_err':>9s} {'first_call_s':>12s}")
    for name, run, oracle in apps:
        want = oracle()
        alone = None
        for fname, fab, pod_axis in fabrics:
            t0 = time.perf_counter()
            got, stats = run(fab, pod_axis)
            wall = time.perf_counter() - t0
            log(f"{name:6s} {fname:9s} {stats.rounds:6d} "
                f"{stats.total_messages:11d} {stats.total_drops:5d} "
                f"{max_err(got, want):9.2e} {wall:12.2f}")
            check(stats.total_drops == 0,
                  f"{name} on {fname}: {stats.total_drops} queue drops")
            check(np.array_equal(got, want), f"{name} on {fname} != oracle")
            if alone is None:
                alone = got
            check(np.array_equal(got, alone),
                  f"{name} on {fname} != first device alone")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    log("multichip: peak bytes in use per device "
        + " ".join(f"{p / 2**30:.2f}GiB" for p in peaks))
    log("phase multichip: ok")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip path on a 2x2 host")
    args = ap.parse_args(argv)
    devices = require_tpu()
    from repro.core.compat import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    hits = []
    jax.monitoring.register_event_listener(
        lambda event, **_: hits.append(1)
        if event == "/jax/compilation_cache/cache_hits" else None)
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)}")
    log(f"compile cache: {cache_dir} ({entries} entries at start)")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {len(devices)}")
    from repro.core.fabric import Fabric
    if args.chips == 1:
        fab = Fabric.single((1,), ("data",))
        graph_phase(GRAPH_SCALE, fab)
        serving_phase(SERVE_SCALE, fab)
        moe_phase(devices[:1])
    else:
        multichip_phase(MULTI_SCALE, devices[:4])
        moe_phase(devices[:4])
    log(f"compile cache: {len(hits)} persistent-cache hits this run")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
