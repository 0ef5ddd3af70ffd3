"""Pareto-guided launch auto-configuration (:mod:`repro.dse.autoconfig`).

Part A — selection properties against the committed ``BENCH_dse.json``:
deterministic for a fixed file, objective ordering respected, and the
acceptance bar: ``config="auto"`` never picks a point whose analytic TEPS
on the quick datasets is below the all-defaults baseline.

Part B — the executable path (subprocess, 8 fake host devices):
``dcra_bfs(g, root, mesh, options=LaunchOptions(config="auto"))`` selects
a frontier point, still
matches the numpy oracle, and the auto-resolved ``QueueConfig`` sizing
stays drop-free at emulation granularity.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.dse.autoconfig import (BASELINE, MINISWEEP_THRESHOLD,
                                  DatasetSignature, autoconfigure,
                                  bench_signatures, interpolate_record,
                                  launch_for, load_bench, objective_score,
                                  objective_weights, select_from_frontier,
                                  signature_distance, signature_of)
from repro.dse.evaluate import evaluate, load_datasets
from repro.sparse import datasets

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def bench():
    b = load_bench()
    assert b is not None, "committed BENCH_dse.json missing"
    return b


@pytest.fixture(scope="module")
def quick_data(bench):
    return load_datasets(int(bench["dataset_scale"]))


# ---------------------------------------------------------------------------
# Part A: signatures
# ---------------------------------------------------------------------------

def test_signature_distance_is_a_premetric():
    a = DatasetSignature(n=256, nnz=4096, skew=1.2)
    assert signature_distance(a, a) == 0.0
    b = DatasetSignature(n=4096, nnz=65536, skew=1.2)
    assert signature_distance(a, b) == signature_distance(b, a) > 0.9


def test_bench_signatures_recompute_matches_recorded(bench):
    if "dataset_signatures" not in bench:
        pytest.skip("bench predates recorded signatures")
    recorded = bench_signatures(bench)
    stripped = {k: v for k, v in bench.items()
                if k != "dataset_signatures"}
    recomputed = bench_signatures(stripped)
    assert set(recorded) == set(recomputed)
    for name in recorded:
        assert recorded[name].n == recomputed[name].n
        assert recorded[name].nnz == recomputed[name].nnz
        assert recorded[name].skew == pytest.approx(recomputed[name].skew)


# ---------------------------------------------------------------------------
# Part A: frontier selection
# ---------------------------------------------------------------------------

def test_selection_is_deterministic_for_a_fixed_bench(bench, quick_data):
    g = quick_data[sorted(quick_data)[0]]
    picks = [autoconfigure(g, "bfs", bench=bench) for _ in range(2)]
    assert picks[0].point == picks[1].point
    assert picks[0].source == picks[1].source == "frontier"
    assert picks[0].score == picks[1].score


def test_selection_respects_the_objective_ordering(bench, quick_data):
    """The frontier argmax really is the argmax of the interpolated
    objective over the app's frontier slice, for every supported
    objective."""
    g = quick_data[sorted(quick_data)[0]]
    sig = signature_of(g)
    sigs = bench_signatures(bench)
    dists = {d: signature_distance(sig, s) for d, s in sigs.items()}
    from repro.dse.autoconfig import frontier_records
    records = frontier_records(bench, "bfs")
    assert records
    for objective in ("teps", "watts", "usd", {"teps": 0.7, "watts": 0.3}):
        weights = objective_weights(objective)
        point, score, _ = select_from_frontier(bench, sig, "bfs", weights)
        scores = [objective_score(weights,
                                  *interpolate_record(r, "bfs", dists))
                  for r in records]
        assert score == pytest.approx(max(scores))


def test_selection_ranks_on_the_app_frontier_slice():
    """Schema v2: when the bench records app-specific Pareto slices, the
    selection for an app considers ONLY that app's slice — a globally
    Pareto point excluded from the slice must not win."""
    sig = DatasetSignature(n=256, nnz=4096, skew=1.0)
    from repro.dse.space import DesignPoint

    def rec(pid, iq, teps):
        return {"point_id": pid, "pareto": True,
                "config": DesignPoint(iq_capacity=iq).to_dict(),
                "metrics": {"teps_geomean": teps, "watts_geomean": 1.0,
                            "system_usd": 100.0},
                "per_cell": {f"{app}:D": {"teps": teps, "seconds": 1.0,
                                          "energy_j": 1.0}
                             for app in ("bfs", "spmv")}}

    bench = {
        "schema": "dcra-dse-bench/v2",
        "dataset_signatures": {"D": sig.to_dict()},
        "datasets": ["D"],
        "points": [rec("slow_bfs_ok", 12, 50.0),
                   rec("fast_global", 48, 100.0)],
        "app_frontiers": {"bfs": ["slow_bfs_ok"],
                          "spmv": ["slow_bfs_ok", "fast_global"]},
    }
    w = objective_weights("teps")
    point, _, dist = select_from_frontier(bench, sig, "bfs", w)
    assert dist == 0.0 and point.iq_capacity == 12   # slice-restricted
    point, _, _ = select_from_frontier(bench, sig, "spmv", w)
    assert point.iq_capacity == 48                   # full slice, argmax
    # an app without a slice falls back to the global frontier
    point, _, _ = select_from_frontier(bench, sig, "wcc", w)
    assert point.iq_capacity == 48


def test_committed_bench_carries_per_app_slices(bench):
    """The regenerated BENCH_dse.json is schema v2 with a non-empty
    frontier slice per swept app (incl. the seventh app, kcore)."""
    assert bench["schema"] == "dcra-dse-bench/v2"
    fronts = bench["app_frontiers"]
    assert set(bench["apps"]) <= set(fronts)
    assert "kcore" in fronts
    ids = {r["point_id"] for r in bench["points"]}
    for app, pids in fronts.items():
        assert pids and set(pids) <= ids


def test_objectives_can_disagree_on_a_synthetic_tradeoff():
    """A fast-but-expensive point vs a cheap-but-slow one: "teps" and
    "usd" must pick different winners."""
    sig = DatasetSignature(n=256, nnz=4096, skew=1.0)
    def point_cfg(iq):
        from repro.dse.space import DesignPoint
        return DesignPoint(iq_capacity=iq).to_dict()
    bench = {
        "dataset_signatures": {"D": sig.to_dict()},
        "datasets": ["D"],
        "points": [
            {"point_id": "fast", "pareto": True, "config": point_cfg(48),
             "metrics": {"teps_geomean": 100.0, "watts_geomean": 10.0,
                         "system_usd": 1000.0},
             "per_cell": {"bfs:D": {"teps": 100.0, "seconds": 1.0,
                                    "energy_j": 10.0}}},
            {"point_id": "cheap", "pareto": True, "config": point_cfg(12),
             "metrics": {"teps_geomean": 50.0, "watts_geomean": 2.0,
                         "system_usd": 100.0},
             "per_cell": {"bfs:D": {"teps": 50.0, "seconds": 1.0,
                                    "energy_j": 2.0}}},
        ],
    }
    pick = {}
    for objective in ("teps", "watts", "usd"):
        w = objective_weights(objective)
        point, _, dist = select_from_frontier(bench, sig, "bfs", w)
        assert dist == 0.0
        pick[objective] = point.iq_capacity
    assert pick["teps"] == 48          # throughput winner
    assert pick["usd"] == 12           # teps/$ winner
    assert pick["watts"] == 12         # power winner


def test_unknown_objective_rejected():
    with pytest.raises(ValueError):
        objective_weights("joules")
    with pytest.raises(ValueError):
        objective_weights({"latency": 1.0})


# ---------------------------------------------------------------------------
# Part A: the acceptance bar — auto never below the all-defaults baseline
# ---------------------------------------------------------------------------

def test_auto_teps_at_least_baseline_on_quick_datasets(bench, quick_data):
    """`config="auto"` (objective teps) must select a frontier point whose
    analytic TEPS on each quick dataset is >= the hand-tuned all-defaults
    deployment the benchmarks launch with."""
    for dname, g in quick_data.items():
        for app in ("bfs", "spmv"):
            lc = autoconfigure(g, app, bench=bench)
            auto = evaluate(lc.point.engine_config(), g, app).teps
            base = evaluate(BASELINE.engine_config(), g, app).teps
            assert auto >= base * (1 - 1e-9), (dname, app, auto, base)


def test_minisweep_fallback_for_faraway_datasets(bench):
    tiny = datasets.erdos_renyi(16, 4, seed=3)
    sig = signature_of(tiny)
    sigs = bench_signatures(bench)
    assert min(signature_distance(sig, s)
               for s in sigs.values()) > MINISWEEP_THRESHOLD
    lc = autoconfigure(tiny, "bfs", bench=bench)
    assert lc.source == "mini-sweep"
    # the baseline is a candidate, so the winner can never score below it
    auto = evaluate(lc.point.engine_config(), tiny, "bfs").teps
    base = evaluate(BASELINE.engine_config(), tiny, "bfs").teps
    assert auto >= base * (1 - 1e-9)


def test_baseline_survives_mini_candidate_truncation():
    """A large frontier (full-space nightly: 10+ Pareto points) must not
    push the all-defaults baseline out of the mini-sweep candidate list —
    it is what anchors the never-below-baseline guarantee."""
    from repro.dse.autoconfig import _mini_candidates
    frontier = [BASELINE.with_(iq_capacity=8 * i) for i in range(2, 16)]
    cands = _mini_candidates(frontier)
    assert len(cands) <= 10
    assert BASELINE in cands


def test_element_stream_signature_lives_in_bin_space():
    """Histogram streams are signatured as (bins, tasks), like the sweep's
    histogram cells — not (len, len), which could never be near any
    recorded graph signature."""
    els = datasets.histogram_data(1 << 12, 64, seed=4)
    sig = signature_of(els)
    assert sig.n == 64 and sig.nnz == len(els)


def test_config_conflicts_with_explicit_sizing_kwargs(quick_data):
    from repro.sparse.jax_apps import dcra_bfs, dcra_spmv
    from repro.sparse.options import LaunchOptions
    g = quick_data[sorted(quick_data)[0]]
    with pytest.raises(ValueError, match="conflicts"):
        dcra_bfs(g, 0, mesh=None, options=LaunchOptions(
            capacity_factor=2.0, config="auto"))
    with pytest.raises(ValueError, match="conflicts"):
        dcra_spmv(g, np.ones(g.n), mesh=None,
                  options=LaunchOptions(cap=4, config="auto"))


# ---------------------------------------------------------------------------
# Part A: MoE dispatch auto-configuration (capacity factor from load)
# ---------------------------------------------------------------------------

def test_moe_autoconfig_uniform_load_picks_the_smallest_factor():
    from repro.core.queues import QueueConfig
    from repro.dse.autoconfig import (MOE_FACTOR_LADDER, autoconfigure_moe,
                                      moe_dispatch_signature)
    E, shards = 16, 8
    # block-cyclic assignment: every (sender, owner-shard) channel carries
    # exactly tasks_per_sender / n_shards tasks — the f=1.0 capacity
    ids = (np.arange(4096) // shards) % E
    sig = moe_dispatch_signature(ids, E)
    assert sig.peak_frac == pytest.approx(1.0 / E)
    f, q = autoconfigure_moe(ids, E, shards)
    assert f == MOE_FACTOR_LADDER[0]
    # the returned QueueConfig IS the dispatch sizing (single source)
    ref_q = QueueConfig.for_moe_dispatch(f)
    for task in ("dispatch", "portal", "expert"):
        assert q.channel_cap(task, 4096, 8) == ref_q.channel_cap(task,
                                                                 4096, 8)


def test_moe_autoconfig_skewed_load_needs_a_larger_factor():
    from repro.dse.autoconfig import autoconfigure_moe, moe_dispatch_signature
    rng = np.random.default_rng(1)
    E, shards, T = 16, 8, 4096
    uniform = rng.integers(0, E, T)
    hot = np.where(rng.random(T) < 0.8, 0, uniform)       # 80% on expert 0
    sig_u = moe_dispatch_signature(uniform, E)
    sig_h = moe_dispatch_signature(hot, E)
    assert sig_h.peak_frac > sig_u.peak_frac
    assert sig_h.cv > sig_u.cv
    f_u, _ = autoconfigure_moe(uniform, E, shards)
    f_h, _ = autoconfigure_moe(hot, E, shards)
    assert f_h > f_u


def test_moe_autoconfig_models_contiguous_token_sharding():
    """moe_dcra shards tokens as contiguous blocks, so a locally
    correlated run (one shard's whole block routed to one expert) must
    raise the factor even when the GLOBAL expert histogram looks mild —
    a round-robin sender model would hide exactly that hotspot."""
    from repro.dse.autoconfig import autoconfigure_moe
    E, shards, T = 16, 8, 4096
    uniform = (np.arange(T) // shards) % E
    correlated = uniform.copy()
    correlated[:T // shards] = 0          # sender 0's block: all expert 0
    f_u, _ = autoconfigure_moe(uniform, E, shards)
    f_c, _ = autoconfigure_moe(correlated, E, shards)
    assert f_c > f_u


def test_moe_autoconfig_is_deterministic_and_handles_empty():
    from repro.dse.autoconfig import MOE_FACTOR_LADDER, autoconfigure_moe
    ids = np.arange(64) % 7
    assert autoconfigure_moe(ids, 8, 4) == autoconfigure_moe(ids, 8, 4)
    f, _ = autoconfigure_moe(np.array([], np.int64), 8, 4)
    assert f == MOE_FACTOR_LADDER[0]


def test_launch_for_wraps_an_explicit_point(quick_data):
    g = quick_data[sorted(quick_data)[0]]
    lc = launch_for(BASELINE, g)
    assert lc.source == "explicit" and lc.point == BASELINE
    assert lc.queues.iq("T3") == BASELINE.iq_capacity
    # device folding: per-shard capacity clamps at the local slice
    q = lc.device_queues(n_dev=8, e_local=500)
    assert q.channel_cap("T3", 500, 8) == 500


# ---------------------------------------------------------------------------
# Part B: the executable path under shard_map (subprocess)
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import numpy as np
from repro.core.compat import make_mesh
from repro.dse.autoconfig import autoconfigure
from repro.sparse import datasets, ref
from repro.sparse.jax_apps import dcra_bfs, dcra_spmv
from repro.sparse.options import LaunchOptions

mesh = make_mesh((8,), ('data',))
g = datasets.rmat(8, edge_factor=16, seed=1)      # a quick-bench dataset
res = {}

lc = autoconfigure(g, 'bfs')
res['source'] = lc.source
res['point_id'] = lc.point.point_id

d, stats = dcra_bfs(g, 0, mesh, options=LaunchOptions(config='auto'))
res['bfs_err'] = float(np.max(np.abs(d - ref.bfs_ref(g, 0))))
res['bfs_drops'] = stats.total_drops
res['bfs_rounds'] = stats.rounds

x = np.random.default_rng(0).random(g.n)
y, drops = dcra_spmv(g, x, mesh, options=LaunchOptions(config='auto'))
want = ref.spmv_ref(g, x)
res['spmv_err'] = float(np.max(np.abs(np.asarray(y) - want))
                        / max(1.0, float(np.abs(want).max())))
res['spmv_drops'] = int(drops)
print('RESULT ' + json.dumps(res))
"""


@pytest.fixture(scope="module")
def exe_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_auto_config_selects_a_frontier_point_for_bench_data(exe_results):
    assert exe_results["source"] == "frontier"


def test_auto_configured_bfs_matches_oracle(exe_results):
    assert exe_results["bfs_err"] == 0.0
    assert exe_results["bfs_drops"] == 0      # device-folded IQ is lossless
    assert 0 < exe_results["bfs_rounds"] < 128


def test_auto_configured_spmv_matches_oracle(exe_results):
    assert exe_results["spmv_err"] < 1e-4
    assert exe_results["spmv_drops"] == 0
