"""Benchmark harness: one module per paper table/figure (DESIGN.md §8).

``python -m benchmarks.run [--scale N] [--quick]`` runs every figure and
prints CSV blocks. --quick uses small graphs (CI); default scale=16 matches
the paper's vertices-per-tile regime (see DESIGN.md §2 scaling note).
"""
from __future__ import annotations

import argparse
import subprocess
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=16)
    ap.add_argument("--quick", action="store_true",
                    help="scale-12 graphs, skip the slowest sweeps")
    args = ap.parse_args()
    scale = 12 if args.quick else args.scale

    # every figure runs in a child process and this parent never imports
    # jax: a process that has touched jax holds the accelerator, and a
    # child would then fail to get it
    def child(mod: str, call: str):
        code = (f"from repro.core.compat import use_compile_cache; "
                f"use_compile_cache(); from benchmarks.{mod} import main; "
                f"main({call})")
        return [sys.executable, "-c", code]

    figs = [(name, child(name, str(scale)))
            for name in ("fig4_topology", "fig5_sram", "fig6_pus",
                         "fig7_freq", "fig8_hbm", "fig10_queues",
                         "fig11_scaling")]
    figs += [
        ("moe_dispatch", child("moe_dispatch", "")),
        # run as __main__: these two pick their own fake-device topology
        # before jax is imported
        ("noc_routing", [sys.executable, "-m", "benchmarks.noc_routing",
                         "--scale", str(min(scale, 11))]),
        ("serve_bench", [sys.executable, "-m", "benchmarks.serve_bench"]
         + (["--smoke", "--devices", "4"] if args.quick else [])),
        ("roofline_table", child("roofline_table", "")),
    ]
    failures = []
    for name, cmd in figs:
        t = time.time()
        print(f"== {name} ==", flush=True)
        rc = subprocess.run(cmd).returncode
        if rc:                  # keep the suite running, but gate at exit
            print(f"{name},ERROR,exit code {rc}", file=sys.stderr)
            failures.append(name)
        print(f"# {name} took {time.time() - t:.1f}s", flush=True)
    if failures:
        print(f"FAILED figures: {', '.join(failures)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
