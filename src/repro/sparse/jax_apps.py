"""Executable JAX implementations of the paper apps — single-device jnp and
*distributed* owner-routed rounds under shard_map.

ALL SEVEN applications (the paper's six, §IV-A, plus k-core
decomposition) are now **TaskProgram definitions**: each app is a ~30-line
declarative spec — edge-payload rule, reduce op, frontier-update rule,
task class — and the shared :func:`repro.sparse.program.run_program`
runtime owns launch/queue resolution, the flat vs pod/portal path, the
cyclic owner layout, the one-round vs ``lax.while_loop`` execution shape,
per-round :class:`~repro.sparse.program.AppStats` and the compile cache.
Program rules are xp-generic, so the SAME definitions drive the analytic
twin (:func:`repro.sparse.program.program_app_stats`) the DSE
revalidation replays through ``TaskEngine.route``.

Layouts mirror DCRA's cyclic PGAS: vertex ``v`` lives on device
``v % n_dev`` at local slot ``v // n_dev``; edges are partitioned by the
owner of their *source* vertex so reading the frontier value is tile-local
and only the per-edge update crosses the NoC (tasks ``(dest, value)`` with
bounded input queues; overflow dropped and counted).

Every app's ``mesh`` argument accepts a :class:`repro.core.fabric.Fabric`
(single-process, fake-device rig or multi-process ``jax.distributed``) or
a raw ``jax.sharding.Mesh`` (deprecated, warn-once shim) — identical
compile-cache keys and bit-identical results either way.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np

from .csr import CSR
from .options import LaunchOptions
# dcra_scatter / from_owner_layout are re-exported: tests and benchmarks
# address the one-round scatter and the layout inverse through this module
from .program import (AppStats, ProgramLaunch, TaskProgram,  # noqa: F401
                      dcra_scatter, from_owner_layout, launch_program,
                      run_program)


# ---------------------------------------------------------------------------
# single-device (edge-parallel) reference executables
# ---------------------------------------------------------------------------

def spmv_jnp(rows, cols, vals, x, n):  # noqa: PLR0917
    return jax.ops.segment_sum(vals * x[cols], rows, num_segments=n)


def histogram_jnp(elements, n_bins):
    return jax.ops.segment_sum(jax.numpy.ones_like(elements), elements,
                               num_segments=n_bins)


def bfs_jnp(rows, cols, n, root,  # noqa: PLR0917
             max_levels: Optional[int] = None):
    """Edge-parallel BFS: one scatter-min round per level."""
    jnp = jax.numpy
    dist = jnp.full((n,), jnp.inf).at[root].set(0.0)

    def round_(level, dist):
        cand = jnp.where(dist[rows] == level, level + 1.0, jnp.inf)
        upd = jax.ops.segment_min(cand, cols, num_segments=n)
        return jnp.minimum(dist, upd)

    levels = max_levels or n
    def body(i, d):
        return round_(jnp.asarray(i, jnp.float32), d)
    return jax.lax.fori_loop(0, levels, body, dist)


# ---------------------------------------------------------------------------
# task streams for the one-round scatter programs
# ---------------------------------------------------------------------------

def spmv_task_stream(g: CSR, x: np.ndarray, n_dev: int, seed: int = 0
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """The exact flat (dest, value) task stream ``dcra_spmv`` routes.

    Device ``d`` owns the contiguous slice ``[d*e_local, (d+1)*e_local)``;
    padding tasks carry ``dest = -1`` (no-task). Exposed so the DSE
    revalidation can feed the *same* stream through the analytic
    ``TaskEngine.route`` twin and compare message/drop counts exactly.

    Edges are shuffled once (host-side): CSR order concentrates a
    high-degree row's edges on one device, overflowing its owner bucket —
    a uniform spread keeps per-owner load near E/(n_dev^2), the same reason
    Dalorex interleaves arrays cyclically.
    """
    E = g.nnz
    perm = np.random.default_rng(seed).permutation(E)
    rows = g.row_of()[perm]
    cols = g.col_idx[perm]
    vals = g.values[perm].astype(np.float32)
    pad = -(-E // n_dev) * n_dev - E
    dest = np.concatenate([rows, np.full(pad, -1)]).astype(np.int32)
    eff = vals * np.asarray(x, np.float32)[cols]
    vals_eff = np.concatenate([eff, np.zeros(pad, np.float32)])
    return dest, vals_eff


def histogram_task_stream(elements: np.ndarray, n_dev: int
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """The flat (dest, value) stream ``dcra_histogram`` routes (see
    :func:`spmv_task_stream` for the sharded-slice convention)."""
    E = len(elements)
    pad = -(-E // n_dev) * n_dev - E
    dest = np.concatenate([np.asarray(elements),
                           np.full(pad, -1)]).astype(np.int32)
    vals = np.concatenate([np.ones(E, np.float32),
                           np.zeros(pad, np.float32)])
    return dest, vals


def _spmv_stream(data, params, n_dev, seed):
    g, x = data
    dest, vals = spmv_task_stream(g, x, n_dev, seed)
    return dest, vals, g.n


def _histogram_stream(data, params, n_dev, seed):
    elements, n_bins = data
    dest, vals = histogram_task_stream(elements, n_dev)
    return dest, vals, n_bins


def _histogram_local_reduce(data, dest, vals, n_items):
    """Single-shard kernel-tier reduce: the histogram kernel counts
    the task stream directly (dest IS the bin id; -1 padding matches no
    bin), replacing the owner-routed ``reduce_received`` round.

    Only consulted by ``run_program`` when no task can drop, so the
    counts are bit-identical to the routed path (differential-tested in
    tests/test_route_kernels.py). Returns None — falling back to the
    routed path — off-TPU at sizes where the interpret-mode kernel would
    be slower than the XLA scatter.
    """
    from ..kernels import ops
    if jax.default_backend() != "tpu" and len(dest) > 4096:
        return None
    counts = ops.histogram(jax.numpy.asarray(dest, jax.numpy.int32),
                           n_items)
    return counts.astype(jax.numpy.float32)


# ---------------------------------------------------------------------------
# program rule library (xp-generic: jnp in-kernel, numpy in the twin)
# ---------------------------------------------------------------------------

def _dist_init(g, params):
    dist = np.full(g.n, np.inf)
    dist[int(params["root"])] = 0.0
    return (dist,), (np.inf,)


def _multi_root_init(g, params):
    """Tenant-column init: ``g`` is a tenant-expanded graph (vertex
    ``t * n + v`` is base vertex ``v`` in tenant ``t``'s column, see
    :func:`repro.serve.batching.tenant_graph`); ``params['roots']`` holds
    one root per tenant. One frontier array carries all T tenants, so a
    single shard_map round serves the whole batch."""
    roots = params["roots"]
    n = g.n // len(roots)
    dist = np.full(g.n, np.inf)
    for t, root in enumerate(roots):
        r = int(root)
        if not 0 <= r < n:
            # a bad root must never wrap into another tenant's column
            raise ValueError(
                f"root {root} out of range [0, {n}) for tenant column {t}")
        dist[t * n + r] = 0.0
    return (dist,), (np.inf,)


def _label_init(g, params):
    return (np.arange(g.n, dtype=np.float64),), (np.inf,)


def _finite_frontier(ctx, state):
    return ctx.xp.isfinite(state[0])


def _all_frontier(ctx, state):
    return ctx.xp.ones(state[0].shape, bool)


def _hops_payload(ctx, state, src_slot, w):
    return state[0][src_slot] + 1.0


def _weight_payload(ctx, state, src_slot, w):
    return state[0][src_slot] + w


def _label_payload(ctx, state, src_slot, w):
    return state[0][src_slot]


def _min_update(ctx, state, frontier, upd):
    new = ctx.xp.minimum(state[0], upd)
    return (new,), new < state[0]


BFS = TaskProgram(name="bfs", reduce_op="min", payload=_hops_payload,
                  init=_dist_init, frontier0=_finite_frontier,
                  update=_min_update, init_only=("root",))

SSSP = TaskProgram(name="sssp", reduce_op="min", payload=_weight_payload,
                   init=_dist_init, frontier0=_finite_frontier,
                   update=_min_update, max_rounds=256,
                   init_only=("root",))

# Tenant-batched serving variants (the resident-serving tier's fused
# multi-root launch, :mod:`repro.serve`): the SAME payload/update rules —
# only init differs, reading per-tenant roots. ``roots`` is init-only, so
# every request batch of one shape class reuses one jitted callable.
BATCHED_BFS = TaskProgram(name="bfs_batched", reduce_op="min",
                          payload=_hops_payload, init=_multi_root_init,
                          frontier0=_finite_frontier, update=_min_update,
                          init_only=("roots",))

BATCHED_SSSP = TaskProgram(name="sssp_batched", reduce_op="min",
                           payload=_weight_payload, init=_multi_root_init,
                           frontier0=_finite_frontier, update=_min_update,
                           max_rounds=256, init_only=("roots",))

WCC = TaskProgram(name="wcc", reduce_op="min", payload=_label_payload,
                  init=_label_init, frontier0=_all_frontier,
                  update=_min_update, undirected=True)


def _pr_init(g, params):
    deg = g.degrees().astype(np.float64)
    rank = np.full(g.n, 1.0 / g.n)
    return (rank, deg, np.ones(g.n)), (0.0, 0.0, 0.0)


def _pr_payload(ctx, state, src_slot, w):
    rank, deg, vmask = state
    contrib = ctx.xp.where(deg > 0, rank / ctx.xp.maximum(deg, 1.0), 0.0)
    return contrib[src_slot]


def _pr_update(ctx, state, frontier, upd):
    rank, deg, vmask = state
    xp = ctx.xp
    damping = ctx.params["damping"]
    inv_n = xp.float32(1.0 / ctx.n)
    dangling = ctx.gsum(xp.sum(
        xp.where((vmask > 0) & (deg == 0), rank, 0.0)))
    rank2 = xp.where(vmask > 0, (1.0 - damping) * inv_n
                     + damping * (upd + dangling * inv_n), 0.0)
    return (rank2, deg, vmask), frontier


PAGERANK = TaskProgram(name="pagerank", reduce_op="add", mode="fixed",
                       active="all", payload=_pr_payload, init=_pr_init,
                       frontier0=_all_frontier, update=_pr_update)

SPMV = TaskProgram(name="spmv", reduce_op="add", mode="single",
                   default_capacity_factor=2.0, stream=_spmv_stream)

HISTOGRAM = TaskProgram(name="histogram", reduce_op="add", mode="single",
                        default_capacity_factor=2.0,
                        stream=_histogram_stream,
                        local_reduce=_histogram_local_reduce)


# ---- k-core decomposition: the seventh app, a pure program definition ----

def _kcore_init(g, params):
    # undirected view: degree counts each stored direction (in + out)
    deg = (g.degrees() + g.transpose().degrees()).astype(np.float64)
    return (deg, np.ones(g.n)), (0.0, 0.0)


def _kcore_frontier0(ctx, state):
    deg, alive = state
    return (alive > 0) & (deg < ctx.params["k"])


def _unit_payload(ctx, state, src_slot, w):
    return ctx.xp.ones(src_slot.shape, ctx.xp.float32)


def _kcore_update(ctx, state, frontier, upd):
    deg, alive = state
    alive2 = ctx.xp.where(frontier, 0.0, alive)   # peeled this round
    deg2 = deg - upd                              # received decrements
    return (deg2, alive2), (alive2 > 0) & (deg2 < ctx.params["k"])


KCORE = TaskProgram(name="kcore", reduce_op="add", undirected=True,
                    payload=_unit_payload, init=_kcore_init,
                    frontier0=_kcore_frontier0, update=_kcore_update)


PROGRAMS = {p.name: p for p in (BFS, SSSP, WCC, PAGERANK, SPMV, HISTOGRAM,
                                KCORE)}


# ---------------------------------------------------------------------------
# public app entry points (thin wrappers over run_program)
# ---------------------------------------------------------------------------

def dcra_spmv(g: CSR, x: np.ndarray, mesh, *,
              options: Optional[LaunchOptions] = None):
    """Distributed y = A @ x via one owner-routed round.

    ``options=`` holds the launch settings (:class:`LaunchOptions`);
    ``config="auto"`` there resolves pod/portal routing and the per-task
    IQ sizing from the tracked Pareto frontier (see
    :mod:`repro.dse.autoconfig`), and ``capacity_factor`` defaults to
    2.0.
    """
    y, stats = run_program(SPMV, (g, x), mesh, dataset=g, options=options)
    return y, stats.total_drops


def dcra_histogram(elements: np.ndarray, n_bins: int, mesh, *,
                   options: Optional[LaunchOptions] = None):
    y, stats = run_program(HISTOGRAM, (elements, n_bins), mesh,
                           dataset=elements, options=options)
    return y, stats.total_drops


def dcra_bfs(g: CSR, root: int, mesh, *,
             options: Optional[LaunchOptions] = None, max_rounds: int = 128
             ) -> Tuple[np.ndarray, AppStats]:
    """Distributed BFS: hop count from root, -1 if unreachable.

    ``options=`` holds the launch settings (:class:`LaunchOptions`):
    ``config="auto"`` picks the deployment (grid, topology, IQ sizing)
    from the tracked Pareto frontier for this graph + objective;
    ``capacity_factor`` (default 4.0) is the manual alternative —
    setting both raises.
    """
    (d,), stats = run_program(BFS, g, mesh, options=options,
                              params={"root": int(root)},
                              max_rounds=max_rounds)
    return np.where(np.isfinite(d), d, -1).astype(np.int64), stats


def dcra_sssp(g: CSR, root: int, mesh, *,
              options: Optional[LaunchOptions] = None, max_rounds: int = 256
              ) -> Tuple[np.ndarray, AppStats]:
    """Distributed SSSP (frontier Bellman-Ford): inf if unreachable."""
    (d,), stats = run_program(SSSP, g, mesh, options=options,
                              params={"root": int(root)},
                              max_rounds=max_rounds)
    return d.astype(np.float64), stats


def dcra_wcc(g: CSR, mesh, *, options: Optional[LaunchOptions] = None,
             max_rounds: int = 128) -> Tuple[np.ndarray, AppStats]:
    """Distributed WCC via min-label propagation over both edge directions."""
    if g.n > (1 << 24):
        # labels ride the f32 NoC payload; ids above 2^24 would collide
        raise ValueError(f"dcra_wcc supports up to 2^24 vertices, got {g.n}")
    (lab,), stats = run_program(WCC, g, mesh, options=options,
                                max_rounds=max_rounds)
    return lab.astype(np.int64), stats


def dcra_pagerank(g: CSR, mesh, damping: float = 0.85, iters: int = 20, *,
                  options: Optional[LaunchOptions] = None
                  ) -> Tuple[np.ndarray, AppStats]:
    """Distributed PageRank: ``iters`` owner-routed epochs (fori_loop),
    dangling mass redistributed uniformly each epoch (matches the oracle)."""
    (rank, _, _), stats = run_program(
        PAGERANK, g, mesh, options=options,
        params={"damping": float(damping), "iters": int(iters)})
    return rank, stats


def dcra_kcore(g: CSR, k: int, mesh, *,
               options: Optional[LaunchOptions] = None, max_rounds: int = 128
               ) -> Tuple[np.ndarray, AppStats]:
    """Distributed k-core decomposition: iterative peel via owner-routed
    degree decrements. Returns each vertex's within-core degree (in+out,
    counting each stored edge direction) or -1 if peeled out of the
    k-core. Oracle: :func:`repro.sparse.ref.kcore_ref`.
    """
    (deg, alive), stats = run_program(
        KCORE, g, mesh, options=options, params={"k": float(k)},
        max_rounds=max_rounds)
    return np.where(alive > 0, deg, -1).astype(np.int64), stats
