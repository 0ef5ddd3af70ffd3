"""Resident serving under sustained mixed-tenant traffic (serving tier).

Drives the :class:`repro.serve.engine.ProgramServer` with a synthetic
multi-tenant request stream (BFS + SSSP roots over resident graphs),
after a one-shot pre-warm of every (program, graph, width) shape class,
and reports the serving metrics: request throughput, per-tenant p50/p99
latency — decomposed into **queue-wait** (submit -> launch) and **device
time** (launch -> harvest) — compile-cache hit rate, fused-launch count,
padding overhead, and the NoC-drop ledger.

``--depth`` sets ``ServeOptions.inflight_depth``: at depth k the server
keeps k fused launches in flight (JAX async dispatch) and forms batch
k+1 while batch k computes. ``--smoke`` is the CI leg: a short stream
that *asserts* the serving invariants (>= 1 compile-cache hit after
warm-up, zero kernel re-traces under load, zero unaccounted drops,
results bit-identical to a standalone launch) and prints ``RESULT ok``;
with ``--depth k > 1`` it additionally runs the same stream at depth 1
and asserts the overlapped responses are bit-identical (results,
statuses, reasons, per-tenant ledger). ``--bench-out BENCH_serve.json``
measures the synchronous drain vs the overlapped drain on one stream and
writes the ``dcra-serve-bench/v1`` trajectory artifact gated by
:mod:`repro.dse.serve_compare`.

``--chaos SEED`` is the chaos-smoke leg: the stream runs fault-free
once, then replays under :func:`repro.serve.seeded_chaos_plan` (one
launch fault, one device-side fault, one host loss that halves the
fabric) with retries and a circuit breaker enabled, and *asserts* the
fault-tolerance contract — every planned fault fired, the ledger stayed
exact, at least one retry and one breaker open/close cycle happened, and
the surviving responses are bit-identical to the fault-free reference.

  PYTHONPATH=src python -m benchmarks.serve_bench [--devices 8]
      [--requests 48] [--tenants 6] [--depth 3] [--fairness drr]
      [--donate] [--smoke] [--chaos SEED] [--fabric]
      [--bench-out BENCH_serve.json]

``--fabric`` drives the whole bench through the :class:`repro.core.fabric`
launch surface (``Fabric.fake`` -> ``ProgramServer(fabric, ...)``) instead
of a raw Mesh; both legs must report identical serving invariants.
"""
from __future__ import annotations

import os
import sys

# Only mutate the device topology when this module IS the program — when
# imported (e.g. by benchmarks.run, which executes it in a subprocess)
# the importer's jax device count must stay untouched. --devices has to
# be pre-scanned: jax fixes the topology at import time.
if (__name__ == "__main__"
        and "host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                               "")):
    _n = 8
    if "--devices" in sys.argv:
        _n = int(sys.argv[sys.argv.index("--devices") + 1])
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count={_n}"
                               ).strip()

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402

import numpy as np   # noqa: E402

from repro.core.compat import make_mesh                      # noqa: E402
from repro.serve import (ProgramServer, Request,             # noqa: E402
                         STATUS_OK, ServeOptions)
from repro.sparse import datasets                            # noqa: E402
from repro.sparse import program as program_mod              # noqa: E402
from repro.sparse.jax_apps import BFS, SSSP                  # noqa: E402
from repro.sparse.program import run_program                 # noqa: E402

from .common import emit                                     # noqa: E402

PROGRAMS = ("bfs", "sssp")
STANDALONE = {"bfs": BFS, "sssp": SSSP}
BENCH_SCHEMA = "dcra-serve-bench/v1"


def make_stream(graphs, tenants: int, requests: int, seed: int = 0):
    """Round-robin tenants over (program, graph) classes, random roots."""
    rng = np.random.default_rng(seed)
    names = sorted(graphs)
    classes = len(PROGRAMS) * len(names)
    reqs = []
    for i in range(requests):
        gname = names[(i // len(PROGRAMS)) % len(names)]
        reqs.append(Request(
            # tenant advances once per full (program, graph) cycle, so
            # same-class requests rotate tenants and batches fuse wide
            req_id=i, tenant=f"tenant{(i // classes) % tenants}",
            program=PROGRAMS[i % len(PROGRAMS)], graph=gname,
            root=int(rng.integers(graphs[gname].n))))
    return reqs


def serve_stream(mesh, graphs, stream, width, serve_options):
    """Pre-warm + run one stream on a fresh server; returns the server
    and the timing/trace envelope."""
    server = ProgramServer(mesh, graphs, batch_width=width,
                           serve_options=serve_options)
    t0 = time.perf_counter()
    server.prewarm(PROGRAMS)
    warm_s = time.perf_counter() - t0
    traces0 = program_mod.cache_stats()["kernel_traces"]
    t0 = time.perf_counter()
    responses = server.run(stream)
    serve_s = time.perf_counter() - t0
    new_traces = program_mod.cache_stats()["kernel_traces"] - traces0
    server.stats.verify()
    return server, responses, warm_s, serve_s, new_traces


def _quant(xs, q):
    if not xs:
        return 0.0
    s = sorted(xs)
    return s[min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))]


def bench_row(mode, opts, responses, serve_s, new_traces, snap):
    """One dcra-serve-bench/v1 row: throughput + the latency split."""
    ok = [r for r in responses if r.status == STATUS_OK]
    return {
        "mode": mode, "depth": opts.inflight_depth,
        "fairness": opts.fairness, "donate": opts.donate_buffers,
        "serve_s": serve_s,
        "throughput_rps": len(responses) / serve_s if serve_s else 0.0,
        "p50_latency_s": _quant([r.latency_s for r in ok], 0.50),
        "p99_latency_s": _quant([r.latency_s for r in ok], 0.99),
        "p50_queue_wait_s": _quant([r.queue_wait_s for r in ok], 0.50),
        "p99_queue_wait_s": _quant([r.queue_wait_s for r in ok], 0.99),
        "p50_device_s": _quant([r.device_s for r in ok], 0.50),
        "p99_device_s": _quant([r.device_s for r in ok], 0.99),
        "launches": snap["launches"],
        "cache_hit_rate": snap["cache_hit_rate"],
        "re_traces": new_traces,
    }


def _signature(responses):
    """The bit-identity signature: results, statuses, reasons — and
    nothing wall-clock."""
    return [(r.req_id, r.tenant, r.status, r.retriable, r.reason,
             None if r.result is None else r.result.tobytes(),
             r.batch_drops, r.batch_messages, r.rounds, r.batch_width)
            for r in sorted(responses, key=lambda r: r.req_id)]


def _ledger(server):
    return {t: (s.submitted, s.served, s.rejected, s.failed)
            for t, s in server.stats.tenants.items()}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8,
                    help="fake CPU devices (applied only when __main__)")
    ap.add_argument("--tenants", type=int, default=6)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--width", type=int, default=4,
                    help="tenant columns per fused launch")
    ap.add_argument("--vertices", type=int, default=192)
    ap.add_argument("--depth", type=int, default=1,
                    help="inflight window depth (1 = synchronous drain)")
    ap.add_argument("--fairness", choices=("fifo", "drr"), default="fifo")
    ap.add_argument("--donate", action="store_true",
                    help="donate retired batch state buffers to the next "
                         "launch of the shape class")
    ap.add_argument("--smoke", action="store_true",
                    help="short CI stream; assert serving invariants")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="run the stream twice — fault-free, then under "
                         "the seeded chaos plan (one launch fault, one "
                         "device fault, one host loss) — and assert the "
                         "chaos run converges to the same responses")
    ap.add_argument("--fabric", action="store_true",
                    help="launch through the Fabric surface instead of a "
                         "raw Mesh")
    ap.add_argument("--bench-out", default=None, metavar="PATH",
                    help="measure sync vs overlapped drain and write the "
                         "dcra-serve-bench/v1 artifact")
    args = ap.parse_args(argv)
    if args.smoke:
        args.tenants = min(args.tenants, 4)
        args.requests = min(args.requests, 16)

    import jax
    n_dev = min(args.devices, len(jax.devices()))
    if args.fabric:
        from repro.core.fabric import Fabric
        mesh = Fabric.fake(n_dev)
    else:
        mesh = make_mesh((n_dev,), ("data",))
    graphs = {
        "wiki": datasets.wiki_like(args.vertices, avg_degree=6, seed=3),
        "er": datasets.erdos_renyi(args.vertices, avg_degree=4, seed=7),
    }
    opts = ServeOptions(inflight_depth=args.depth, fairness=args.fairness,
                        donate_buffers=args.donate)
    stream = make_stream(graphs, args.tenants, args.requests)

    if args.chaos is not None:
        # The chaos-smoke leg: a fault-free reference sizes the plan (its
        # launch count bounds the injectable indices), then the SAME
        # stream replays under the seeded plan with retries + a breaker.
        # Every fault must fire, exactly one host loss must shrink the
        # fabric, and the surviving responses must converge bit-identical
        # to the reference — min-reduce programs don't care how many
        # devices finished the job. The --smoke zero-re-trace assert does
        # NOT apply here: the shrink re-prewarms the affected classes.
        from repro.serve import seeded_chaos_plan
        ref_srv, ref_resp, _, _, _ = serve_stream(
            mesh, graphs, stream, args.width, ServeOptions())
        n_ref = ref_srv.stats.snapshot()["launches"]
        plan = seeded_chaos_plan(args.chaos, n_ref,
                                 keep_devices=max(1, n_dev // 2))
        planned = dict(plan.at)
        chaos_opts = ServeOptions(inflight_depth=args.depth,
                                  fairness=args.fairness,
                                  max_retries=3, breaker_threshold=1)
        srv = ProgramServer(mesh, graphs, batch_width=args.width,
                            serve_options=chaos_opts, failure_plan=plan)
        srv.prewarm(PROGRAMS)
        responses = srv.run(stream)
        srv.stats.verify()
        snap = srv.stats.snapshot()

        def reduced(rs):
            return [(r.req_id, r.tenant, r.status, r.retriable,
                     None if r.result is None else r.result.tobytes())
                    for r in sorted(rs, key=lambda r: r.req_id)]

        assert plan.exhausted, f"unfired faults: {plan.at}"
        assert [k for _, k in plan.fired] == [planned[i]
                                              for i in sorted(planned)], \
            f"fault order diverged from the plan: {plan.fired}"
        assert snap["host_losses"] == 1, snap
        assert snap["retries"] > 0, "no request ever retried"
        assert snap["breaker_opens"] >= 1 and snap["breaker_closes"] >= 1, \
            snap
        assert all(r.status == STATUS_OK for r in responses), \
            [r.reason for r in responses if r.status != STATUS_OK]
        assert reduced(responses) == reduced(ref_resp), \
            "chaos responses diverged from the fault-free reference"
        assert _ledger(srv) == _ledger(ref_srv), \
            "chaos per-tenant ledger diverged from the fault-free reference"
        print(f"# chaos seed={args.chaos} plan={planned} "
              f"retries={snap['retries']} "
              f"breaker_opens={snap['breaker_opens']} "
              f"devices {n_dev} -> {srv.fabric.n_devices}")
        print("RESULT chaos ok")
        return

    if args.bench_out:
        # sync vs overlapped on the SAME stream — the trajectory artifact
        sync_opts = ServeOptions(inflight_depth=1)
        over_opts = ServeOptions(inflight_depth=max(2, args.depth),
                                 fairness=args.fairness,
                                 donate_buffers=args.donate)
        rows = []
        sigs = []
        for mode, o in (("sync", sync_opts), ("overlapped", over_opts)):
            srv, resp, _, serve_s, tr = serve_stream(
                mesh, graphs, stream, args.width, o)
            rows.append(bench_row(mode, o, resp, serve_s, tr,
                                  srv.stats.snapshot()))
            sigs.append(_signature(resp))
        assert sigs[0] == sigs[1], \
            "overlapped responses diverged from the synchronous drain"
        speedup = rows[1]["throughput_rps"] / rows[0]["throughput_rps"]
        bench = {
            "schema": BENCH_SCHEMA,
            "backend": jax.default_backend(),
            "config": {"devices": n_dev, "width": args.width,
                       "tenants": args.tenants, "requests": args.requests,
                       "vertices": args.vertices,
                       "depth": over_opts.inflight_depth,
                       "fairness": over_opts.fairness,
                       "donate": over_opts.donate_buffers},
            "rows": rows,
            "overlap_speedup": speedup,
        }
        with open(args.bench_out, "w") as f:
            json.dump(bench, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.bench_out}: overlapped "
              f"{rows[1]['throughput_rps']:.1f} req/s vs sync "
              f"{rows[0]['throughput_rps']:.1f} req/s "
              f"({speedup:.2f}x, depth={over_opts.inflight_depth})")
        return

    server, responses, warm_s, serve_s, new_traces = serve_stream(
        mesh, graphs, stream, args.width, opts)
    snap = server.stats.snapshot()
    rows = [(t, s["submitted"], s["served"], s["rejected"], s["failed"],
             f"{s['p50_latency_s'] * 1e3:.1f}",
             f"{s['p99_latency_s'] * 1e3:.1f}",
             f"{s['p50_queue_wait_s'] * 1e3:.1f}",
             f"{s['p50_device_s'] * 1e3:.1f}")
            for t, s in sorted(snap["tenants"].items())]
    emit(rows, "tenant,submitted,served,rejected,failed,p50_ms,p99_ms,"
               "p50_wait_ms,p50_device_ms")
    print(f"# devices={n_dev} width={args.width} depth={args.depth} "
          f"fairness={args.fairness} "
          f"surface={'fabric' if args.fabric else 'mesh'} "
          f"prewarm={warm_s:.1f}s "
          f"serve={serve_s:.1f}s "
          f"throughput={args.requests / serve_s:.1f} req/s")
    print(f"# launches={snap['launches']} "
          f"batched={snap['batched_requests']} "
          f"pad_columns={snap['pad_columns']} "
          f"cache_hit_rate={snap['cache_hit_rate']:.2f} "
          f"re_traces={new_traces} noc_drops={snap['noc_drops']} "
          f"p50_round={snap['p50_round_latency_s'] * 1e3:.1f}ms "
          f"p99_round={snap['p99_round_latency_s'] * 1e3:.1f}ms")

    if args.smoke:
        assert all(r.status == STATUS_OK for r in responses), \
            [r.reason for r in responses if r.status != STATUS_OK]
        assert snap["cache_hits"] >= 1, snap
        assert new_traces == 0, f"{new_traces} re-traces under load"
        assert snap["noc_drops"] == 0, snap   # default sizing is drop-free
        # one spot-check: the batched column matches a standalone launch
        r0 = responses[0]
        (ref,), _ = run_program(STANDALONE[stream[0].program],
                                graphs[stream[0].graph], mesh,
                                params={"root": stream[0].root})
        assert np.array_equal(np.asarray(r0.result), np.asarray(ref)), \
            "batched result != standalone"
        if args.depth > 1:
            # the overlapped leg: the same stream at depth 1 must produce
            # bit-identical responses AND ledger, with zero re-traces
            ref_srv, ref_resp, _, _, ref_traces = serve_stream(
                mesh, graphs, stream, args.width, ServeOptions())
            assert ref_traces == 0, f"{ref_traces} re-traces (sync leg)"
            assert _signature(responses) == _signature(ref_resp), \
                f"depth={args.depth} responses != synchronous drain"
            assert _ledger(server) == _ledger(ref_srv), \
                f"depth={args.depth} ledger != synchronous drain"
        print("RESULT ok")


if __name__ == "__main__":
    from repro.core.compat import use_compile_cache
    use_compile_cache()
    main()
