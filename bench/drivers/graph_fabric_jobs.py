"""Graph-analytics jobs on a fabric of several chips, one at a time, back
to back.

The jobs, the window, the spans and the check are ``graph_jobs``'s (its
``_jobs`` and ``_check``, and the same loop): ``launch_program(...)`` then
``.result()`` on the configuration's fabric, every job checked against the
plain oracle in ``bench/ref/graph.py`` after the window. Traffic
parameters are ``graph_jobs``'s too.

The record adds what only a fabric of several chips has:

* ``scope_s``: in a traced run, the device self time of each program
  scope in the window, per chip (``bench/scopes.py``, from the HLO of the
  executables the warm-up job compiled or loaded), so the all-to-all
  (``dcra.route.a2a``) and the per-round scalar collectives
  (``dcra.graph.sync``, where the program has that scope) are read apart;
* ``n_dev`` and, per job, ``cross_messages``: the out-edges of the
  vertices the oracle reached (each BFS level's vertices send along all of
  theirs in the round after they are reached) whose two ends have
  different owners, vertex ``v`` living on chip ``v mod n_dev``
  (``bench/work_fabric.py``);
* ``wire``: the program's ``wire_plan`` of the launch (the wire slots each
  chip ships per round), where the program has one.
"""
from __future__ import annotations

import contextlib
import sys
import time

from bench import scopes, work_fabric
from bench import trace as bench_trace
from bench.drivers import graph_jobs as gj

#: the host spans whose device busy time the metrics read
BUSY_SPANS = gj.BUSY_SPANS


@contextlib.contextmanager
def _kept_oracle(kept: dict):
    """The oracle's BFS hop counts, kept by root while ``_check`` asks for
    them, so that counting cross-chip messages runs no oracle twice."""
    from bench.ref import graph as ref
    bfs = ref.bfs

    def keep(g, root):
        kept[root] = bfs(g, root)
        return kept[root]

    ref.bfs = keep
    try:
        yield kept
    finally:
        ref.bfs = bfs


def _wire(csr, fab) -> dict | None:
    """The launch's ``WirePlan`` as a dict, or None where the program has
    no ``wire_plan``."""
    from repro.sparse import program
    from repro.sparse.jax_apps import BFS
    plan = getattr(program, "wire_plan", None)
    return None if plan is None else plan(BFS, csr, fab)._asdict()


def run(run) -> dict:
    from repro.core.fabric import Fabric
    from repro.sparse.csr import CSR
    from bench.ref import rmat
    cfg, tr = run.cfg, run.traffic
    if tr["app"] != "bfs":
        raise ValueError(f"graph_fabric_jobs runs bfs jobs, not {tr['app']!r}")
    t = time.perf_counter()
    g = rmat.graph(cfg, run.seed)
    csr = CSR(g.row_ptr, g.col_idx, g.values)
    fab = Fabric.single(tuple(cfg["fabric"]["shape"]),
                        tuple(cfg["fabric"]["axes"]))
    n_dev = fab.n_devices
    launch, warm, param = gj._jobs(run, g, csr, fab)
    t_graph = time.perf_counter()
    with scopes.capture_hlo() as texts:
        launch(warm).result()                 # compiles, or loads the cache
    wire = _wire(csr, fab)
    print(f"bench: set-up: start {t - run.t_start:.3f} s, graph V={g.n} "
          f"E={g.nnz} on {n_dev} chips {t_graph - t:.3f} s, warm-up job "
          f"{time.perf_counter() - t_graph:.3f} s; wire {wire}",
          file=sys.stderr)

    jobs, answers = [], []
    with run.window():
        t0 = time.perf_counter()
        while True:
            p = param(len(jobs))
            with run.span("bench.job"):
                ts = time.perf_counter()
                with run.span("bench.launch"):
                    job = launch(p)
                tl = time.perf_counter()
                with run.span("bench.harvest"):
                    states, stats = job.result()
                te = time.perf_counter()
            jobs.append({"param": p, "launch_s": tl - ts, "job_s": te - ts,
                         "rounds": stats.rounds,
                         "messages": stats.messages.tolist(),
                         "drops": stats.total_drops})
            answers.append(states[0])
            if te - t0 >= run.window_seconds:
                break
    window_s = te - t0
    peak = run.memory_peak()
    del job, states
    print("bench: jobs (rounds, launch s, job s): " + " ".join(
        f"{j['rounds']},{j['launch_s']:.3f},{j['job_s']:.3f}" for j in jobs),
        file=sys.stderr)

    record = {"jobs": jobs, "n_vertices": g.n, "n_dev": n_dev,
              "work": tr["work"]}
    if wire is not None:
        record["wire"] = wire
    if run.trace:
        scoped = scopes.read(run.trace_dir, texts)
        (win,) = bench_trace.spans(scoped.trace, bench_trace.WINDOW_SPAN)
        record["scope_s"] = scopes.scope_self_s(scoped, win.start, win.end)

    with _kept_oracle({}) as dist:
        checks, failed = gj._check(run, g, jobs, answers)
    cross = work_fabric.cross_degrees(g, n_dev)
    for j in jobs:
        j["cross_messages"] = work_fabric.cross_messages(cross, dist[j["param"]])
    return {"attempted": len(jobs), "failed": failed,
            "end_to_end": {"job_s": window_s / len(jobs)},
            "memory_peak_bytes": peak, "checks": checks, "record": record}
