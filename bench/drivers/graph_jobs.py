"""Graph-analytics jobs, one at a time, back to back.

Traffic parameters (``bench/traffic/<name>.json``):

* ``app``: ``bfs`` (one root per job, drawn from the seed among the
  vertices of degree >= 1, ``roots`` of them, cycled) or ``pagerank``
  (``damping``, ``iters`` fixed rounds);
* ``max_rounds``: the BFS round bound the program's ``dcra_bfs`` passes;
* ``work``: the words the algorithm must move, for the round roofline:
  ``msg_words`` per message, ``state_words_read`` / ``_written`` per vertex
  per round;
* ``limits``: the limit of each number compared.

The graph comes from the configuration's Graph500 generator and the seed,
cut to the configuration's edge count (``bench/ref/rmat.py``). A job is what a user of the program runs:
``launch_program(...)`` then ``.result()``, exactly ``run_program``. The
window runs whole jobs: it ends when the job running at ``--seconds``
returns its result. Afterwards every job of the window is checked against
the plain oracle in ``bench/ref/graph.py``.
"""
from __future__ import annotations

import sys
import time

import numpy as np

#: the host spans whose device busy time the metrics read
BUSY_SPANS = ("bench.job",)


def _jobs(run, g, csr, fab):
    """(launch(i), warm-up launch, per-job parameter) for the traffic."""
    from repro.sparse import program
    from repro.sparse.jax_apps import BFS, PAGERANK
    tr = run.traffic
    rng = np.random.default_rng(run.seed)
    if tr["app"] == "bfs":
        cand = np.flatnonzero(g.degrees() > 0)
        roots = [int(r) for r in rng.choice(cand, tr["roots"] + 1,
                                            replace=False)]
        warm = roots.pop()

        def launch(root):
            return program.launch_program(BFS, csr, fab,
                                          params={"root": root},
                                          max_rounds=tr["max_rounds"])
        return launch, warm, lambda i: roots[i % len(roots)]
    if tr["app"] == "pagerank":
        params = {"damping": float(tr["damping"]), "iters": int(tr["iters"])}

        def launch(_):
            return program.launch_program(PAGERANK, csr, fab, params=params)
        return launch, None, lambda i: None
    raise ValueError(f"unknown graph app {tr['app']!r}")


def _check(run, g, jobs, answers) -> tuple:
    """The numbers compared, and how many jobs failed."""
    from bench.ref import graph as ref
    tr, limits = run.traffic, run.traffic["limits"]
    drops = sum(j["drops"] for j in jobs)
    if tr["app"] == "bfs":
        wrong = [int(np.count_nonzero(
            np.where(np.isfinite(a), a, -1) != ref.bfs(g, j["param"])))
            for j, a in zip(jobs, answers)]
        checks = {"hops_wrong": (sum(wrong), limits["hops_wrong"])}
    else:
        want = ref.pagerank(g, tr["damping"], tr["iters"])
        errs = [ref.max_rel_err(a, want) for a in answers]
        wrong = [e > limits["rank_max_rel_err"] for e in errs]
        checks = {"rank_max_rel_err": (max(errs), limits["rank_max_rel_err"])}
    checks["drops"] = (drops, limits["drops"])
    failed = sum(1 for j, w in zip(jobs, wrong) if w or j["drops"])
    return checks, failed


def run(run) -> dict:
    from repro.core.fabric import Fabric
    from repro.sparse.csr import CSR
    from bench.ref import rmat
    cfg = run.cfg
    t = time.perf_counter()
    g = rmat.graph(cfg, run.seed)
    csr = CSR(g.row_ptr, g.col_idx, g.values)
    fab = Fabric.single(tuple(cfg["fabric"]["shape"]),
                        tuple(cfg["fabric"]["axes"]))
    launch, warm, param = _jobs(run, g, csr, fab)
    t_graph = time.perf_counter()
    launch(warm).result()                     # compiles, or loads the cache
    print(f"bench: set-up: start {t - run.t_start:.3f} s, graph V={g.n} "
          f"E={g.nnz} {t_graph - t:.3f} s, warm-up job "
          f"{time.perf_counter() - t_graph:.3f} s", file=sys.stderr)

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

    checks, failed = _check(run, g, jobs, answers)
    return {"attempted": len(jobs), "failed": failed,
            "end_to_end": {"job_s": window_s / len(jobs)},
            "memory_peak_bytes": peak, "checks": checks,
            "record": {"jobs": jobs, "n_vertices": g.n,
                       "work": run.traffic["work"]}}
