"""The controls that the check of a cell with an expert share must catch.

Each puts, in the program's ``moe_dcra``, what a later change might be
tempted to ship, and runs the cell's own harness over it; ``correct`` must
come out false:

* ``fp8_reference``: the plain reference (``bench/ref/moe_share.py``) with
  its expert products, routed and shared, in float8 e4m3, the precision
  below the bfloat16 the configuration states;
* ``no_group_limit``: the program choosing the plain top-k of all experts,
  without the limit to the best groups;
* ``no_shared_expert``: the program leaving the shared expert out.

    python3 bench/control_share.py --workload <cell> --seconds <s> --seeds 1 2 3

runs every control on each seed on this machine's chip and prints one JSON
line of readings per control and seed. ``bench/test_bench_share.py`` runs
them at a small size on the CPU.
"""
import sys
from pathlib import Path

if __name__ == "__main__":
    _root = Path(__file__).resolve().parents[1]
    sys.path[:1] = [str(_root / "src"), str(_root)]

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

from bench import control  # noqa: E402

REF_BLOCK = 4096   # tokens per block of the float8 reference


def fp8_reference():
    import jax
    from bench.ref import moe_share as ref

    def out(orig, params, x, cfg, info):
        mc = cfg.moe
        first, held = info.expert_share
        layer = ref.Layer(n_experts=mc.num_experts, first=first, held=held,
                          top_k=mc.top_k, n_group=mc.n_group,
                          topk_group=mc.topk_group,
                          scaling=mc.routed_scaling_factor, eps=cfg.norm_eps)
        tokens = x.reshape(-1, x.shape[-1])
        block = min(REF_BLOCK, tokens.shape[0])
        y = jax.lax.map(lambda xb: ref.moe(params, xb, layer, fp8=True)[0],
                        tokens.reshape(-1, block, x.shape[-1]))
        return y.reshape(x.shape).astype(x.dtype)
    return control._moe(out)


def _program_with(**moe_fields):
    def out(orig, params, x, cfg, info):
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, **moe_fields))
        return orig(params, x, cfg, info)[0]
    return control._moe(out)


def no_group_limit():
    return _program_with(n_group=1, topk_group=1)


def no_shared_expert():
    return _program_with(n_shared=0)


CONTROLS = (fp8_reference, no_group_limit, no_shared_expert)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import run as bench_run
    spec = bench_run.load_spec(args.workload)
    devices = bench_run.require_chips(int(spec["cell"]["chips"]))
    from repro.core.compat import use_compile_cache
    use_compile_cache()
    for seed in args.seeds:
        for ctl in CONTROLS:
            t = time.perf_counter()
            with ctl():
                res = bench_run.run_cell(spec, seed, args.seconds, False,
                                         devices)
            print(json.dumps({"workload": args.workload,
                              "control": ctl.__name__, "seed": seed,
                              "correct": res["correct"],
                              "attempted": res["attempted"],
                              "checks": res["checks"],
                              "seconds": time.perf_counter() - t}),
                  flush=True)


if __name__ == "__main__":
    main()
