"""Graph analytics on the DCRA task engine: all seven apps (the paper's
six + k-core) on one dataset, with the paper's target metrics (TEPS,
TEPS/W, TEPS/$) and the design-space comparison the paper advocates
(SRAM-only vs HBM packaging).

``--distributed`` additionally runs every app on the REAL distributed
shard_map path — over every device JAX finds (8 fake host devices under
``JAX_PLATFORMS=cpu``) — as a TaskProgram through the shared owner-routed
NoC layer in ``repro.core.routing``, validating each against its numpy
oracle and printing per-app rounds / routed messages / IQ drops.

  PYTHONPATH=src python examples/graph_analytics.py [--scale 12]
      [--distributed]
"""
import argparse
import os
import sys

if (any(a.startswith("--dist") for a in sys.argv)  # argparse abbreviations
        and os.environ.get("JAX_PLATFORMS") == "cpu"
        and "host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                               "")):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()

import numpy as np

from repro.core import EngineConfig, TaskEngine, TileGrid
from repro.core.cache import DRAMConfig, SRAMConfig
from repro.costmodel import run_energy, run_perf
from repro.sparse import apps, datasets, ref

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.common import config_cost, evaluate, APPS  # noqa: E402


def run_distributed(g, scale):
    """All seven apps on the shard_map path; oracle-checked, stats
    printed."""
    import jax
    from repro.core.fabric import Fabric
    from repro.sparse.jax_apps import (dcra_bfs, dcra_histogram,
                                       dcra_kcore, dcra_pagerank,
                                       dcra_spmv, dcra_sssp, dcra_wcc)
    from repro.sparse.options import LaunchOptions
    n_dev = len(jax.devices())
    mesh = Fabric.single((n_dev,), ("data",))
    x = np.random.default_rng(0).random(g.n)
    els = datasets.histogram_data(1 << 14, 256)
    hdr = f"{'app':10s} {'rounds':>7s} {'messages':>10s} {'drops':>7s} " \
          f"{'max_err':>10s}"
    print(f"distributed path ({n_dev} {jax.devices()[0].platform} devices, "
          f"owner-routed rounds)")
    print(hdr)
    print("-" * len(hdr))

    def row(name, got, want, stats):
        err = float(np.max(np.abs(np.asarray(got, np.float64) -
                                  np.asarray(want, np.float64))))
        print(f"{name:10s} {stats.rounds:7d} {stats.total_messages:10d} "
              f"{stats.total_drops:7d} {err:10.2e}")

    from repro.sparse.jax_apps import AppStats
    wide = LaunchOptions(capacity_factor=3.0)
    y, drops = dcra_spmv(g, x, mesh, options=wide)
    one = AppStats(1, np.array([g.nnz]), np.array([int(drops)]))
    row("spmv", y, ref.spmv_ref(g, x), one)
    h, drops = dcra_histogram(els, 256, mesh, options=wide)
    one = AppStats(1, np.array([len(els)]), np.array([int(drops)]))
    row("histogram", h, ref.histogram_ref(els, 256), one)
    d, st = dcra_bfs(g, 0, mesh)
    row("bfs", d, ref.bfs_ref(g, 0), st)
    s, st = dcra_sssp(g, 0, mesh)
    row("sssp", np.where(np.isfinite(s), s, -1),
        np.where(np.isfinite(ref.sssp_ref(g, 0)), ref.sssp_ref(g, 0), -1),
        st)
    p, st = dcra_pagerank(g, mesh)
    row("pagerank", p, ref.pagerank_ref(g), st)
    w, st = dcra_wcc(g, mesh)
    row("wcc", w, ref.wcc_ref(g), st)
    k, st = dcra_kcore(g, 16, mesh)
    row("kcore", k, ref.kcore_ref(g, 16), st)
    print()

    # Pareto-guided launch: pick the deployment from the tracked frontier
    # instead of hand-tuning capacity_factor (repro.dse.autoconfig)
    from repro.dse.autoconfig import autoconfigure
    lc = autoconfigure(g, "bfs")
    print(f"auto-config (bfs, objective=teps): {lc.point.point_id} "
          f"[{lc.source}]")
    # reuse the resolved config
    d, st = dcra_bfs(g, 0, mesh, options=LaunchOptions(config=lc))
    row("bfs[auto]", d, ref.bfs_ref(g, 0), st)
    print()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--distributed", action="store_true",
                    help="also run the six apps on the shard_map path")
    args = ap.parse_args()
    from repro.core.compat import use_compile_cache
    use_compile_cache()

    g = datasets.rmat(args.scale, edge_factor=16)
    print(f"RMAT-{args.scale}: V={g.n} E={g.nnz} "
          f"({g.memory_bytes() / 2**20:.1f} MB CSR)\n")

    if args.distributed:
        run_distributed(g, args.scale)

    packagings = {
        "DCRA-HBM (32x32)": EngineConfig(
            grid=TileGrid(32, 32, "hier_torus", die_rows=16, die_cols=16),
            sram=SRAMConfig(kb_per_tile=512), dram=DRAMConfig(present=True)),
        "DCRA-SRAM (64x64)": EngineConfig(
            grid=TileGrid(64, 64, "hier_torus", die_rows=16, die_cols=16),
            sram=SRAMConfig(kb_per_tile=512), dram=DRAMConfig(present=False)),
    }
    hdr = f"{'packaging':20s} {'app':10s} {'TEPS':>10s} {'TEPS/W':>10s} " \
          f"{'TEPS/$':>10s}"
    print(hdr)
    print("-" * len(hdr))
    for pname, cfg in packagings.items():
        for app in APPS:
            r = evaluate(cfg, g, app)
            print(f"{pname:20s} {app:10s} {r.teps:10.2e} "
                  f"{r.teps_per_watt:10.2e} {r.teps_per_dollar:10.2e}")
        print()


if __name__ == "__main__":
    main()
