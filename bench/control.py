"""The controls and faults that ``correct`` must catch, and their readings.

A control puts, in the program's place, what a later change might be
tempted to ship, and runs the cell's own harness over it; ``correct`` must
come out false:

* ``small_queue`` (BFS): the program's own input queues cut to a quarter
  (``capacity_factor=0.25``), below a peak round's messages, so tasks drop:
  it breaks the configuration's guarantee that no task is dropped;
* ``bf16_reference`` (PageRank): the plain reference with its state in
  bfloat16, the precision below the float32 the program states;
* ``fp8_reference`` (MoE): the plain reference with its expert products in
  float8 e4m3, the precision below the bfloat16 the configuration states.

Faults break the timed path where it is produced:

* ``unchanged``: a job answers its initial state, a layer returns its input;
* ``half_batch``: a layer leaves out half of its batch (zeros there);
* ``altered``: one answer of a job, or one token of a step, altered.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

runs the cell's control on each seed on this machine's chip and prints one
JSON line of readings per seed. ``bench/test_bench_control.py`` runs them
all at a small size on the CPU.
"""
import sys
from pathlib import Path

if __name__ == "__main__":
    _root = Path(__file__).resolve().parents[1]
    sys.path[:1] = [str(_root / "src"), str(_root)]

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

SMALL_QUEUE = 0.25


class _Answer:
    """A finished launch whose answer is replaced."""

    def __init__(self, states, stats):
        self._result = (states, stats)

    def result(self):
        return self._result


@contextlib.contextmanager
def _patched(module, name, make):
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def _graph(make_answer):
    """Patch ``launch_program`` so each job's answer is
    ``make_answer(prog, data, params, states)``."""
    from repro.sparse import program

    def make(orig):
        def launch(prog, data, fabric, **kw):
            states, stats = orig(prog, data, fabric, **kw).result()
            return _Answer(make_answer(prog, data, kw.get("params") or {},
                                       states), stats)
        return launch
    return _patched(program, "launch_program", make)


def _moe(make_out):
    """Patch ``moe_dcra`` so the layer returns ``make_out(orig, params, x,
    cfg, info)``."""
    from repro.core import dispatch

    def make(orig):
        def moe_dcra(params, x, cfg, info, queues=None):
            return make_out(orig, params, x, cfg, info), 0.0
        return moe_dcra
    return _patched(dispatch, "moe_dcra", make)


def small_queue():
    from repro.sparse import program
    from repro.sparse.options import LaunchOptions

    def make(orig):
        def launch(prog, data, fabric, **kw):
            opts = LaunchOptions(capacity_factor=SMALL_QUEUE)
            return orig(prog, data, fabric, options=opts, **kw)
        return launch
    return _patched(program, "launch_program", make)


def bf16_reference():
    from bench.ref import graph as ref
    from bench.ref.rmat import Graph

    def answer(prog, data, params, states):
        g = Graph(data.row_ptr, data.col_idx, data.values)
        ranks = ref.pagerank(g, params["damping"], params["iters"], bf16=True)
        return (ranks,) + tuple(states[1:])
    return _graph(answer)


def fp8_reference():
    from bench.ref import moe as ref

    def out(orig, params, x, cfg, info):
        y, _ = ref.forward(params, x, cfg.moe.top_k, fp8=True)
        return y.reshape(x.shape).astype(x.dtype)
    return _moe(out)


def unchanged(kind):
    if kind == "graph":
        def answer(prog, data, params, states):
            states0, _ = prog.init(data, params)
            return tuple(np.asarray(s, np.float64) for s in states0)
        return _graph(answer)
    return _moe(lambda orig, params, x, cfg, info: x)


def half_batch(kind):
    def out(orig, params, x, cfg, info):
        y, _ = orig(params, x, cfg, info)
        return y.at[x.shape[0] // 2:].set(0)
    return _moe(out)


def altered(kind):
    if kind == "graph":
        def answer(prog, data, params, states):
            first = np.array(states[0], np.float64)
            v = int(np.flatnonzero(np.isfinite(first))[-1])
            first[v] = first[v] * 2 + 1
            return (first,) + tuple(states[1:])
        return _graph(answer)

    def out(orig, params, x, cfg, info):
        y, _ = orig(params, x, cfg, info)
        return y.at[0, 0].multiply(-1)
    return _moe(out)


#: the control of each driver app
CONTROLS = {"bfs": small_queue, "pagerank": bf16_reference,
            "moe_layer": fp8_reference}
#: the faults each kind of cell can have (one chip: no exchange between
#: chips to leave out)
FAULTS = {"graph": (unchanged, altered),
          "moe": (unchanged, half_batch, altered)}


def app_of(traffic: dict) -> str:
    return traffic.get("app", traffic["driver"])


def kind_of(traffic: dict) -> str:
    return "graph" if traffic["driver"] == "graph_jobs" else "moe"


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
    control = CONTROLS[app_of(spec["traffic"])]
    for seed in args.seeds:
        t = time.perf_counter()
        with control():
            res = bench_run.run_cell(spec, seed, args.seconds, False, devices)
        print(json.dumps({"workload": args.workload, "control": control.__name__,
                          "seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"], "checks": res["checks"],
                          "seconds": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
