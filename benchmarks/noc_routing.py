"""Single-stage vs hierarchical (pod/portal) owner-routed NoC collectives.

Runs the shared routing layer (:mod:`repro.core.routing`) both ways on the
same task streams — one flat all_to_all over all devices vs the paper's
§III-A two-stage tile-NoC / die-NoC path — and reports wall-clock,
IQ-overflow drops, and the analytic die-crossing count from the topology
model (the quantity the portal aggregation exists to cut).

  PYTHONPATH=src python -m benchmarks.noc_routing [--devices 8] [--scale 11]
"""
from __future__ import annotations

import os

# Only mutate the device topology when this module IS the program — when
# imported (e.g. by benchmarks.run, which executes it in a subprocess) the
# importer's jax device count must stay untouched.
if (__name__ == "__main__"
        and "host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                               "")):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()

import argparse      # noqa: E402
import time          # noqa: E402

import numpy as np   # noqa: E402

from repro.core import EngineConfig, TaskEngine, TileGrid   # noqa: E402
from repro.core.compat import make_mesh                      # noqa: E402
from repro.sparse import datasets, ref                       # noqa: E402
from repro.sparse.jax_apps import (dcra_bfs, dcra_histogram,  # noqa: E402
                                   dcra_spmv)
from repro.sparse.options import LaunchOptions               # noqa: E402

from .common import emit                                     # noqa: E402


def _timed(fn, reps=5):
    y, d = fn()                      # compile + correctness sample
    np.asarray(y)
    t = time.perf_counter()
    for _ in range(reps):
        y, d = fn()
        np.asarray(y)
    return (time.perf_counter() - t) / reps * 1e3, int(d), y


def die_crossings(dest, n_dev, n_pods):
    """Analytic die-NoC crossings for the same stream (topology model)."""
    grid = TileGrid(1, n_dev, "hier_torus", die_rows=1,
                    die_cols=n_dev // n_pods)
    eng = TaskEngine(EngineConfig(grid=grid), int(dest.max()) + 1)
    valid = dest >= 0
    src = (np.arange(len(dest)) % n_dev)[valid]   # edge-parallel src shards
    rs = eng.route("T3", src_idx=src, dst_idx=dest[valid])
    return rs.die_crossings


def _bfs_stats(g, mesh, pod_axis=None):
    d, st = dcra_bfs(g, 0, mesh, options=LaunchOptions(
        pod_axis=pod_axis, capacity_factor=4.0))
    return d.astype(np.float64), st.total_drops


def main(scale: int = 11, n_dev: int = 8, n_pods: int = 2):
    flat = make_mesh((n_dev,), ("data",))
    hier = make_mesh((n_pods, n_dev // n_pods), ("pod", "data"))

    g = datasets.rmat(scale, edge_factor=8, seed=3)
    x = np.random.default_rng(0).random(g.n)
    els = datasets.histogram_data(1 << 16, 1 << 10)

    one = LaunchOptions(capacity_factor=3.0)
    two = one.with_(pod_axis="pod")
    rows = []
    for name, fn_flat, fn_hier, oracle in (
        ("spmv",
         lambda: dcra_spmv(g, x, flat, options=one),
         lambda: dcra_spmv(g, x, hier, options=two),
         ref.spmv_ref(g, x)),
        ("histogram",
         lambda: dcra_histogram(els, 1 << 10, flat, options=one),
         lambda: dcra_histogram(els, 1 << 10, hier, options=two),
         ref.histogram_ref(els, 1 << 10)),
        # iterative TaskPrograms route hierarchically too: every
        # while_loop round re-enters the two-stage pod/portal collective
        ("bfs",
         lambda: _bfs_stats(g, flat),
         lambda: _bfs_stats(g, hier, pod_axis="pod"),
         ref.bfs_ref(g, 0).astype(np.float64)),
    ):
        for mode, fn in (("single_stage", fn_flat), ("hierarchical", fn_hier)):
            ms, drops, y = _timed(fn)
            err = float(np.max(np.abs(np.asarray(y, np.float64) - oracle)))
            rows.append(("noc_routing", name, mode, f"{ms:.2f}ms",
                         f"drops={drops}", f"err={err:.2e}"))
    dest = g.row_of()
    rows.append(("noc_routing", "analytic", "die_crossings_flat",
                 die_crossings(dest, n_dev, n_dev), "", ""))
    rows.append(("noc_routing", "analytic", "die_crossings_hier",
                 die_crossings(dest, n_dev, n_pods), "", ""))
    emit(rows, "figure,app,mode,ms_per_round,drops,err")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--scale", type=int, default=11)
    a = ap.parse_args()
    from repro.core.compat import use_compile_cache
    use_compile_cache()
    main(scale=a.scale, n_dev=a.devices, n_pods=a.pods)
