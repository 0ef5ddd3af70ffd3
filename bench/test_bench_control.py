"""The controls and faults of bench/control.py make ``correct`` come out
false, and the program itself comes out correct, through the harness's own
run at a small size on the CPU (the look for a chip is skipped)."""
import contextlib

import jax
import pytest

from bench import control
from bench import run as bench_run

SMALL = {"graph500-rmat18": {"scale": 10, "edges": 14000},
         "olmoe-1b-7b-moe": {"hidden_size": 128, "intermediate_size": 64,
                             "num_experts": 8, "num_experts_per_tok": 2}}
SMALL_TRAFFIC = {"moe_layer": {"batch": 2, "seq": 64}}
CELLS = ["graph500-bfs", "ldbc-pagerank", "olmoe-layer-fwd"]
SEED = 2**31 + 77


def _run(cell, patch=None):
    spec = bench_run.load_spec(cell)
    spec["config"].update(SMALL[spec["cell"]["config"]])
    spec["traffic"].update(SMALL_TRAFFIC.get(spec["traffic"]["driver"], {}))
    with patch() if patch else contextlib.nullcontext():
        return bench_run.run_cell(spec, SEED, 0.3, False, jax.devices())


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    traffic = bench_run.load_spec(cell)["traffic"]
    res = _run(cell, control.CONTROLS[control.app_of(traffic)])
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell,fault", [
    (cell, fault) for cell in CELLS
    for fault in control.FAULTS[control.kind_of(
        bench_run.load_spec(cell)["traffic"])]],
    ids=lambda v: v if isinstance(v, str) else v.__name__)
def test_fault_is_not_correct(cell, fault):
    traffic = bench_run.load_spec(cell)["traffic"]
    res = _run(cell, lambda: fault(control.kind_of(traffic)))
    assert not res["correct"], res["checks"]
