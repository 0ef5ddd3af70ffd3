"""Subprocess worker: re-validate analytic winners on the real shard_map
executables — for EVERY app, iterative ones included.

The DSE sweep's analytic stack models the bounded input queue of the
distributed routing layer (:mod:`repro.core.routing`); this worker proves
the model on a top-K point by routing the *same* task stream through both
paths at the same parallelism and comparing message / drop counts:

* executable: the ``dcra_*`` apps from :mod:`repro.sparse.jax_apps` under
  ``shard_map`` on ``n_dev`` host devices, with the point's IQ capacity
  pinned via ``cap=`` (a ``QueueConfig.from_cap`` override under the
  hood);
* analytic: each app's **TaskProgram twin**
  (:func:`repro.sparse.program.program_app_stats`) — the program's
  generated task stream replayed round by round through
  ``TaskEngine.route`` with ``QueueConfig(default_iq=cap)`` on a
  ``TileGrid(1, n_dev)`` — one tile per shard, so the per-(source shard →
  owner) channel structure is identical (the property
  ``tests/test_routing.py`` pins). For the iterative apps the twin
  evolves vertex state under the executable's own kept/dropped admission
  order, so the per-round streams (and therefore drop counts) agree
  exactly even when tight queues lose updates mid-run.

The ``histogram_self`` app is the heavy self-traffic case: every shard's
element stream targets mostly bins the shard itself owns, so overflow lands
on the (d -> d) channels — proving the analytic model's same-tile drop
charging matches the executable ``bucket``'s treatment of self-owned tasks.

Must run in its own process: the fake-device count has to be set before
jax imports (same pattern as ``benchmarks/noc_routing.py``). Protocol:
spec JSON on stdin, one ``RESULT <json>`` line on stdout.

Spec::

    {"n_dev": 8, "scale": 8, "seed": 0,
     "checks": [{"point_id": "...", "iq_capacity": 12,
                 "apps": ["spmv", "histogram", "bfs", "sssp", "wcc",
                          "pagerank", "kcore"]}]}
"""
from __future__ import annotations

import os

if "XLA_FLAGS" not in os.environ:  # must precede any jax import
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json     # noqa: E402
import sys      # noqa: E402

import numpy as np  # noqa: E402

RESULT_PREFIX = "RESULT "

# the iterative (graph-program) apps and their revalidation parameters
PROGRAM_PARAMS = {
    "bfs": {"root": 0},
    "sssp": {"root": 0},
    "wcc": {},
    "pagerank": {"damping": 0.85, "iters": 5},
    "kcore": {"k": 8.0},
}


def _analytic_counts(dest: np.ndarray, n: int, fab, cap: int):
    """The same stream through the analytic twin at shard parallelism
    (``fab.tile_grid()`` — one tile per shard)."""
    from ..core.queues import QueueConfig
    from ..core.task_engine import EngineConfig, TaskEngine
    n_dev = fab.n_devices
    engine = TaskEngine(EngineConfig(grid=fab.tile_grid(),
                                     queues=QueueConfig(default_iq=cap)), n)
    e_local = len(dest) // n_dev
    shard_of = np.repeat(np.arange(n_dev), e_local)
    valid = dest >= 0
    rs = engine.route("T3", src_idx=shard_of[valid],
                      dst_idx=dest[valid].astype(np.int64))
    return rs.tasks_total, rs.drops


def check_point(check: dict, n_dev: int, scale: int, seed: int) -> list:
    import jax.numpy as jnp
    from ..core.fabric import Fabric
    from ..sparse import datasets
    from ..sparse.jax_apps import (dcra_histogram, dcra_scatter, dcra_spmv,
                                   histogram_task_stream, spmv_task_stream)
    from ..sparse.options import LaunchOptions

    fab = Fabric.fake(n_dev)
    mesh = fab             # every launch below goes through the Fabric path
    cap = max(1, int(check["iq_capacity"]))  # honored exactly, no rounding
    g = datasets.rmat(scale, edge_factor=8, seed=1)
    out = []
    for app in check.get("apps", ("spmv", "histogram")):
        if app == "spmv":
            x = np.random.default_rng(seed).random(g.n)
            dest, _ = spmv_task_stream(g, x, n_dev, seed)
            _, dropped = dcra_spmv(g, x, mesh,
                                   options=LaunchOptions(seed=seed, cap=cap))
            n_items = g.n
            # measure delivered-task count END TO END: route unit payloads
            # through the same collective so kept+dropped is observed at
            # the owners, not recomputed from the host-side stream
            ones = np.ones(len(dest), np.float32)
            y1, drop1 = dcra_scatter(jnp.asarray(dest), jnp.asarray(ones),
                                     n_items, mesh, op="add",
                                     options=LaunchOptions(cap=cap))
            kept = int(round(float(np.asarray(y1).sum())))
            assert int(drop1) == int(dropped)   # same stream, same cap
        elif app == "histogram":
            els = datasets.histogram_data(g.nnz, max(g.n // 16, 64),
                                          seed=seed + 3)
            n_items = max(g.n // 16, 64)
            dest, _ = histogram_task_stream(els, n_dev)
            y, dropped = dcra_histogram(els, n_items, mesh,
                                        options=LaunchOptions(cap=cap))
            # the histogram IS a unit-payload scatter: its own output
            # counts the delivered tasks
            kept = int(round(float(np.asarray(y).sum())))
        elif app == "histogram_self":
            # heavy self-traffic: ~90% of each shard's elements hash to
            # bins the shard itself owns (bin % n_dev == shard), so IQ
            # overflow concentrates on the same-tile (d -> d) channels
            n_items = max(g.n // 16, 64)
            e_local = max(g.nnz // n_dev, 32)
            rng = np.random.default_rng(seed + 7)
            shard_of = np.repeat(np.arange(n_dev), e_local)
            bins = rng.integers(0, max(n_items // n_dev, 1),
                                n_dev * e_local) * n_dev
            self_mask = rng.random(n_dev * e_local) < 0.9
            owner = np.where(self_mask, shard_of,
                             rng.integers(0, n_dev, n_dev * e_local))
            els = np.minimum(bins + owner, n_items - 1)
            dest, _ = histogram_task_stream(els, n_dev)
            y, dropped = dcra_histogram(els, n_items, mesh,
                                        options=LaunchOptions(cap=cap))
            kept = int(round(float(np.asarray(y).sum())))
        elif app in PROGRAM_PARAMS:
            # iterative app: run the whole program, compare the per-round
            # message/drop trajectories against the TaskProgram twin
            from ..sparse.jax_apps import PROGRAMS
            from ..sparse.program import program_app_stats, run_program
            params = PROGRAM_PARAMS[app]
            _, stats = run_program(
                PROGRAMS[app], g, mesh,
                options=LaunchOptions(cap=cap, seed=seed), params=params)
            twin = program_app_stats(PROGRAMS[app], g, n_dev, cap=cap,
                                     params=params, seed=seed)
            ok = (stats.rounds == twin.rounds
                  and np.array_equal(stats.messages, twin.messages)
                  and np.array_equal(stats.drops, twin.drops))
            out.append({
                "point_id": check.get("point_id", ""),
                "app": app, "n_dev": n_dev, "cap": cap,
                "executable": {"messages": stats.total_messages,
                               "drops": stats.total_drops,
                               "rounds": stats.rounds},
                "analytic": {"messages": twin.total_messages,
                             "drops": twin.total_drops,
                             "rounds": twin.rounds},
                "ok": ok,
            })
            continue
        else:
            raise ValueError(f"unsupported revalidation app {app!r}")
        exe_drops = int(dropped)
        exe_msgs = kept + exe_drops
        ana_msgs, ana_drops = _analytic_counts(dest, n_items, fab, cap)
        ok = (exe_msgs == ana_msgs) and (exe_drops == ana_drops)
        out.append({
            "point_id": check.get("point_id", ""),
            "app": app, "n_dev": n_dev, "cap": cap,
            "executable": {"messages": exe_msgs, "drops": exe_drops},
            "analytic": {"messages": ana_msgs, "drops": ana_drops},
            "ok": ok,
        })
    return out


def main() -> int:
    spec = json.load(sys.stdin)
    n_dev = int(spec.get("n_dev", 8))
    scale = int(spec.get("scale", 8))
    seed = int(spec.get("seed", 0))
    results = []
    for check in spec["checks"]:
        results.extend(check_point(check, n_dev, scale, seed))
    print(RESULT_PREFIX + json.dumps(results), flush=True)
    return 0 if all(r["ok"] for r in results) else 3


if __name__ == "__main__":
    sys.exit(main())
