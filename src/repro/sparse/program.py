"""The TaskProgram runtime — ONE engine executes every sparse app.

The paper frames every workload as owner-routed tasks flowing over a
software-configured network; a :class:`TaskProgram` is the software
equivalent of that claim (Tascade / Nexus Machine's task / active-message
program abstraction): an app is a ~30-line *spec* — edge-payload rule,
reduce op, frontier-update rule, convergence predicate, task class — and
:func:`run_program` owns everything the apps used to duplicate:

* launch resolution from one :class:`~repro.sparse.options.LaunchOptions`
  (``config=`` included);
* :class:`~repro.core.queues.QueueConfig` capacity resolution + clamping
  (via the shared :func:`~repro.core.routing.resolve_caps` against the
  launch :class:`~repro.core.fabric.Fabric`);
* flat vs pod/portal path selection (iterative apps route hierarchically
  now, not just the one-round scatters);
* the cyclic owner layout pack/unpack, with each graph's edges packed
  once and kept resident (see :func:`packed_graph`);
* the one-round vs ``lax.while_loop`` / ``lax.fori_loop`` execution shape
  with per-round :class:`AppStats`;
* a **compile cache** keyed by (program, shapes, mesh, capacities) so
  repeated same-shape launches reuse the jitted shard_map callable
  instead of re-tracing (see :func:`cache_stats`).

Program rules are **xp-generic**: they receive a :class:`Ctx` whose
``xp`` is ``jax.numpy`` inside the shard_map kernel and plain ``numpy``
in the analytic twin, so one rule definition drives both paths. The twin
(:func:`program_app_stats` / :func:`program_rounds`) host-simulates the
*same* rounds — same packed-edge admission order, same
first-``cap``-per-channel keep rule the shard_map ``bucket`` applies,
kept-only state updates — and replays each round's task stream through
``TaskEngine.route``, which is what lets ``repro.dse.shardcheck``
revalidate *every* app (not just the one-round scatters) with exact
message/drop agreement.
"""
from __future__ import annotations

import itertools
import weakref
from dataclasses import dataclass
from typing import Callable, Dict, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.compat import shard_map_unchecked
from ..core.fabric import Fabric, as_fabric
from ..core.queues import QueueConfig, round8
from ..core.routing import (local_route, owner_route,
                            owner_route_finish, owner_route_hier,
                            owner_route_hier_start, owner_route_start,
                            reduce_received, resolve_caps,
                            resolve_flat_cap, resolve_hier_caps, route_wire)
from .options import LaunchOptions
from ..core.task_engine import (EngineConfig, RoundStats, RunStats,
                                TaskEngine)
from ..core.topology import TileGrid


# ---------------------------------------------------------------------------
# per-round instrumentation (the executable twin of RunStats)
# ---------------------------------------------------------------------------

@dataclass
class AppStats:
    """Per-round NoC counters from a distributed run.

    ``messages`` counts routed tasks per round (including owner-local ones —
    they occupy IQ slots just the same); ``drops`` counts IQ-overflow
    discards. Convert with :meth:`to_run_stats` for the cost model.
    """
    rounds: int
    messages: np.ndarray          # [rounds] int64
    drops: np.ndarray             # [rounds] int64

    @property
    def total_messages(self) -> int:
        return int(self.messages.sum())

    @property
    def total_drops(self) -> int:
        return int(self.drops.sum())

    def to_run_stats(self, payload_words: int = 2,
                     word_bytes: int = 8) -> RunStats:
        rs = RunStats()
        for m, d in zip(self.messages.tolist(), self.drops.tolist()):
            rs.rounds.append(RoundStats(
                messages=int(m),
                payload_bytes=int(m) * payload_words * word_bytes,
                tasks_total=int(m),
                drops=int(d)))
        return rs


def _collect_stats(rounds, msgs, drops) -> AppStats:
    r = int(rounds)
    return AppStats(rounds=r,
                    messages=np.asarray(msgs)[:r].astype(np.int64),
                    drops=np.asarray(drops)[:r].astype(np.int64))


# ---------------------------------------------------------------------------
# the program spec
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Ctx:
    """What a program rule sees, on either execution substrate.

    ``xp`` is ``jax.numpy`` inside the shard_map kernel and ``numpy`` in
    the analytic twin; ``gsum`` is the cross-shard scalar sum (``psum``
    under shard_map, identity in the twin, whose arrays are global).
    Rules must use ``ctx.gsum(ctx.xp.sum(...))`` for global reductions so
    one definition is correct on both substrates.
    """
    xp: object
    n: int                       # global item count
    n_dev: int
    params: Mapping
    gsum: Callable


@dataclass(frozen=True)
class TaskProgram:
    """Declarative spec of one DCRA sparse app.

    Graph programs define ``init`` / ``frontier0`` / ``payload`` /
    ``update`` (xp-generic rules, see :class:`Ctx`); one-round stream
    programs define only ``stream``. Vertex state is a tuple of f32
    arrays in the cyclic owner layout; the runtime owns routing,
    reduction, stats and the loop shape.

    Convergence for ``mode="while"`` is the universal frontier predicate:
    the loop continues while any shard's frontier is non-empty (and
    ``r < max_rounds``); ``mode="fixed"`` runs ``params["iters"]`` epochs.
    """
    name: str                              # autoconfig app key
    reduce_op: str = "min"                 # "add" | "min"
    mode: str = "while"                    # "while" | "fixed" | "single"
    undirected: bool = False               # route both edge directions
    active: str = "frontier"               # "frontier" | "all" edges emit
    task: str = "T3"                       # QueueConfig task class
    default_capacity_factor: float = 4.0
    max_rounds: int = 128                  # "while" bound (overridable)
    # Params consumed ONLY by the host-side ``init`` rule (e.g. BFS/SSSP
    # roots): excluded from the compile-cache key AND stripped from the
    # traced kernel's Ctx, so same-shape launches that differ only in
    # these params reuse the jitted callable (a rule that reads one
    # anyway fails loudly with a KeyError at trace time). This is what
    # makes the serving tier's per-request roots cache-transparent.
    init_only: Tuple[str, ...] = ()
    # graph rules ----------------------------------------------------------
    init: Optional[Callable] = None        # (g, params) -> (states, fills)
    frontier0: Optional[Callable] = None   # (ctx, state) -> bool mask
    payload: Optional[Callable] = None     # (ctx, state, src_slot, w) -> vals
    update: Optional[Callable] = None      # (ctx, state, frontier, upd)
    #                                      #   -> (state2, frontier2)
    # stream rule ----------------------------------------------------------
    stream: Optional[Callable] = None      # (data, params, n_dev, seed)
    #                                      #   -> (dest, vals, n_items)
    # optional kernel-tier local reduce for single-shard stream launches:
    # (data, dest, vals, n_items) -> y or None (None = use the routed
    # path). Only consulted when no task can drop (cap >= e_local), so
    # the result — and the analytic twin — stay bit-identical.
    local_reduce: Optional[Callable] = None


# ---------------------------------------------------------------------------
# cyclic owner layout (vertex v -> device v % n_dev, slot v // n_dev)
# ---------------------------------------------------------------------------

def owner_layout(arr_n, n_dev):
    """Reorder a dense [n] array into cyclic-owner order (device-major)."""
    n = arr_n.shape[0]
    n_local = -(-n // n_dev)
    idx = jnp.arange(n_local * n_dev)
    src = (idx % n_local) * n_dev + idx // n_local   # device-major -> global
    valid = src < n
    return jnp.where(valid, arr_n[jnp.minimum(src, n - 1)], 0), valid


def from_owner_layout(y_sharded, n, n_dev):
    """Inverse of owner_layout: [n_local*n_dev] -> global order [n]."""
    n_local = -(-n // n_dev)
    g = jnp.arange(n)
    pos = (g % n_dev) * n_local + g // n_dev
    return y_sharded[pos]


def _owner_pack_np(arr, n_dev, fill):
    """numpy owner_layout with a chosen fill for the padding slots."""
    arr = np.asarray(arr, np.float64)
    n = len(arr)
    n_local = -(-n // n_dev)
    idx = np.arange(n_local * n_dev)
    g = (idx % n_local) * n_dev + idx // n_local
    valid = g < n
    out = np.full(n_local * n_dev, fill, np.float64)
    out[valid] = arr[g[valid]]
    return out, valid


# ---------------------------------------------------------------------------
# edge packing (host-side, shared with the analytic twin)
# ---------------------------------------------------------------------------

def _pack_edges(rows, cols, wts, n_dev, seed=0):  # noqa: PLR0917
    """Partition edges by src-vertex owner (device-major flat arrays).

    Returns (src_slot, dst, w, E_max): each [n_dev * E_max]; padding edges
    carry dst = -1 (owner_route treats them as no-task). Edges are
    shuffled once so owner buckets fill uniformly, then grouped by owner
    with a single stable argsort + cumcount (no per-device python loop);
    the stable sort preserves the shuffled order within each device — the
    bucket admission order the analytic twin mirrors.
    """
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(rows))
    rows, cols, wts = rows[perm], cols[perm], wts[perm]
    own = (rows % n_dev).astype(np.int64)
    order = np.argsort(own, kind="stable")
    rows, cols, wts, own = rows[order], cols[order], wts[order], own[order]
    counts = np.bincount(own, minlength=n_dev)
    E_max = max(8, int(counts.max(initial=0)))
    starts = np.repeat(np.r_[0, np.cumsum(counts)[:-1]], counts)
    pos = np.arange(len(rows)) - starts
    flat = own * E_max + pos
    src_slot = np.zeros(n_dev * E_max, np.int32)
    dst = np.full(n_dev * E_max, -1, np.int32)
    w = np.zeros(n_dev * E_max, np.float32)
    src_slot[flat] = (rows // n_dev).astype(np.int32)
    dst[flat] = cols.astype(np.int32)
    w[flat] = wts
    return src_slot, dst, w, E_max


def _graph_setup(g, n_dev, undirected=False, seed=0):
    rows, cols, wts = g.row_of(), g.col_idx.astype(np.int64), g.values
    if undirected:
        rows, cols = np.concatenate([rows, cols]), np.concatenate([cols,
                                                                   rows])
        wts = np.concatenate([wts, wts])
    src_slot, dst, w, E_max = _pack_edges(rows, cols, wts, n_dev, seed)
    n_local = -(-g.n // n_dev)
    return n_local, src_slot, dst, w, E_max


def _owner_bound(dst, n_dev):
    """The most valid edges (``dst >= 0``) that one device's block of the
    packed ``dst`` holds for one owner ``dst % n_dev``: the most tasks
    that one (sender, owner) bucket of a flat round can ever be offered."""
    blocks = dst.reshape(n_dev, -1)
    return max(int(np.bincount(b[b >= 0] % n_dev, minlength=n_dev).max())
               for b in blocks)


# ---------------------------------------------------------------------------
# resident packed graphs: one pack per (graph, device count), kept
# ---------------------------------------------------------------------------

class PackedGraph:
    """A graph's edges packed for ``n_dev`` devices, kept while the graph
    lives.

    The host part is the :func:`_graph_setup` result (``n_local``,
    ``src_slot`` / ``dst`` / ``w``, ``E_max``), which the analytic twin
    reads, and ``owner_bound`` (:func:`_owner_bound`), which caps a flat
    round's buckets (:func:`_owner_caps`). The device part is the same
    three arrays placed on a fabric's mesh, once per (fabric, partition
    spec): every launch hands them to the jitted call as its edge
    arguments, which are never donated. Every caller shares the host
    arrays, so they are read-only.
    """

    def __init__(self, setup):
        self.n_local, self.src_slot, self.dst, self.w, self.E_max = setup
        for a in (self.src_slot, self.dst, self.w):
            a.setflags(write=False)
        self.owner_bound = _owner_bound(self.dst,
                                        len(self.dst) // self.E_max)
        self._placed: Dict[tuple, tuple] = {}

    def edges_on(self, fab: Fabric, spec) -> tuple:
        """``(src_slot, dst, w)`` laid out on ``fab`` under ``spec``."""
        key = (fab.fabric_key(), spec)
        got = self._placed.get(key)
        if got is None:
            got = tuple(_to_global(fab, spec, a)
                        for a in (self.src_slot, self.dst, self.w))
            self._placed[key] = got
        return got


# (graph id, n_dev, undirected, seed) -> (weakref to the graph, its
# (row_ptr, col_idx, values), PackedGraph). A lookup is a hit only when
# the referent IS the argument and its three arrays are the same objects,
# so neither id() reuse nor a reassigned field serves a stale pack; the
# weakref callback purges a collected graph's entries.
_PACKED: Dict[tuple, tuple] = {}


def packed_graph(g, n_dev: int, undirected: bool = False,
                 seed: int = 0) -> PackedGraph:
    """The resident :class:`PackedGraph` of ``g`` for ``n_dev`` devices,
    packed on first use (``CACHE_STATS["graph_packs"]``) and found again
    on every later one (``["graph_pack_hits"]``).

    Packing marks the graph's three arrays read-only, so an in-place edit
    of a packed graph raises instead of leaving its pack stale: a changed
    graph is a new ``CSR``.
    """
    key = (id(g), n_dev, bool(undirected), seed)
    fields = (g.row_ptr, g.col_idx, g.values)
    got = _PACKED.get(key)
    if (got is not None and got[0]() is g
            and all(a is b for a, b in zip(got[1], fields))):
        CACHE_STATS["graph_pack_hits"] += 1
        return got[2]
    CACHE_STATS["graph_packs"] += 1
    for a in fields:
        a.setflags(write=False)
    pg = PackedGraph(_graph_setup(g, n_dev, undirected=undirected,
                                  seed=seed))
    ref = weakref.ref(g, lambda _r, _k=key: _PACKED.pop(_k, None))
    _PACKED[key] = (ref, fields, pg)
    return pg


# ---------------------------------------------------------------------------
# launch resolution (LaunchOptions.config) — shared by every app
# ---------------------------------------------------------------------------

def resolve_launch(config, g, app, objective="teps"):
    """Resolve a launch's ``LaunchOptions.config`` to a ``LaunchConfig``
    (or None).

    ``"auto"`` runs the Pareto-guided selection in
    :mod:`repro.dse.autoconfig`; a ``LaunchConfig`` passes through; a
    ``DesignPoint`` is wrapped as an explicit choice. ``None`` keeps the
    sizing of ``cap`` / ``capacity_factor`` / ``queues``
    (:meth:`LaunchOptions.resolve` refuses ``config`` beside them).
    """
    if config is None:
        return None
    from ..dse.autoconfig import LaunchConfig, autoconfigure, launch_for
    if isinstance(config, str):
        if config != "auto":
            raise ValueError(f"unknown config {config!r} (expected 'auto', "
                             f"a LaunchConfig or a DesignPoint)")
        return autoconfigure(g, app, objective=objective)
    if isinstance(config, LaunchConfig):
        return config
    return launch_for(config, g, objective=objective)


def _resolve_queues(prog: TaskProgram, queues, cap, capacity_factor):
    if queues is not None:
        return queues
    if cap is not None:
        return QueueConfig.from_cap(cap, prog.task)
    if capacity_factor is None:
        capacity_factor = prog.default_capacity_factor
    return QueueConfig.from_factor(capacity_factor, prog.task)


def _graph_caps(queues: QueueConfig, task: str,  # noqa: PLR0917
                e_local: int, n_dev: int,
                pods: Optional[Tuple[int, int]]) -> Tuple[int, ...]:
    """Per-round capacities for a graph program, flat or pod/portal.

    Explicit caps are only defined for the flat path (same rule as
    ``dcra_scatter``); the flat cap is allocation-clamped at ``e_local``.
    """
    if queues.iq_sizes.get(task) is not None and pods is not None:
        raise ValueError("explicit cap is only defined for the flat path")
    if pods is None:
        return (resolve_flat_cap(queues, task, e_local, n_dev, clamp=True),)
    n_intra, n_pods = pods
    return resolve_hier_caps(queues, task, e_local, n_intra, n_pods)


def _owner_caps(pg: PackedGraph, caps: Tuple[int, ...],
                pods: Optional[Tuple[int, int]]) -> Tuple[int, ...]:
    """A flat round's cap clamped at ``round8(pg.owner_bound)``.

    In a round each active edge sends one task to its destination's owner,
    so the bucket from device c to owner d is never offered more tasks
    than c's block has edges to d. A cap at or above that bound admits
    every task, so the clamp shrinks the allocation (the wire and the
    receive buffer) and never the admission: drop counts, and the analytic
    twin, are the same either way. The pod/portal caps pass unchanged.
    """
    if pods is not None:
        return caps
    return (min(caps[0], round8(pg.owner_bound)),)


# ---------------------------------------------------------------------------
# multi-process I/O adapters (no-ops on every single-process fabric)
# ---------------------------------------------------------------------------

def _to_global(fab: Fabric, spec, arr):
    """Lay a host-global array out on the fabric's mesh under ``spec``,
    each device receiving only its own shard.

    On one process ``jax.device_put`` onto ``NamedSharding(fab.mesh,
    spec)`` uploads every shard straight to its device, so the jitted call
    is handed its inputs already laid out as its ``in_specs`` say (not
    staged whole on the first device for jit to reshard). On a
    multi-process fabric a host numpy array cannot feed a global-mesh jit
    directly, so it is wrapped with ``make_array_from_callback``: every
    process holds the same global values (the packed inputs are
    deterministic from the seed), and each callback slices out the shards
    this process owns.
    """
    sharding = NamedSharding(fab.mesh, spec)
    if not fab.is_multiprocess:
        return jax.device_put(arr, sharding)
    a = np.asarray(arr)
    return jax.make_array_from_callback(a.shape, sharding, lambda idx: a[idx])


def _host_gather(fab: Fabric, x):
    """One sharded output back to every host, as numpy (global order).
    Single-process: plain pass-through (no extra host copy — callers keep
    operating on the sharded jax array exactly as before)."""
    if not fab.is_multiprocess:
        return x
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


# ---------------------------------------------------------------------------
# the compile cache
# ---------------------------------------------------------------------------

_CACHE: Dict[tuple, Callable] = {}
CACHE_STATS = {"hits": 0, "misses": 0, "kernel_traces": 0,
               "local_fold_builds": 0, "owner_capped_builds": 0,
               "graph_packs": 0, "graph_pack_hits": 0}


def cache_stats() -> Dict[str, int]:
    """Copy of the compile-cache counters (asserted by tests: a repeated
    same-shape launch must be a ``hits`` increment with ``kernel_traces``
    unchanged — no re-trace). ``local_fold_builds`` counts the graph
    callables built with the one-device local fold (see
    :func:`_build_graph_fn`), ``owner_capped_builds`` those built with a
    flat cap that the graph's owner bound lowered (see
    :func:`_owner_caps`). ``graph_packs`` counts the resident
    :class:`PackedGraph` handles built and ``graph_pack_hits`` the
    lookups that found one (see :func:`packed_graph`): N jobs on one
    graph read 1 and N - 1."""
    return dict(CACHE_STATS)


def clear_cache() -> None:
    """Empty the compile cache and the packed-graph memo, and zero the
    counters."""
    _CACHE.clear()
    _PACKED.clear()
    for k in CACHE_STATS:
        CACHE_STATS[k] = 0


def _mesh_key(mesh_or_fabric):
    """Legacy alias — the cache identity now lives on the fabric
    (:meth:`repro.core.fabric.Fabric.fabric_key`, byte-compatible)."""
    return Fabric.of(mesh_or_fabric).fabric_key()


def _cached(key, build):
    fn = _CACHE.get(key)
    if fn is None:
        CACHE_STATS["misses"] += 1
        fn = _CACHE[key] = build()
    else:
        CACHE_STATS["hits"] += 1
    return fn


def cache_keys() -> Tuple[tuple, ...]:
    """The live compile-cache keys (the serving tier asserts pre-warm
    populates exactly the expected shape classes)."""
    return tuple(_CACHE)


def prewarm_program(prog: TaskProgram, data, fabric, **kwargs) -> Tuple[
        tuple, ...]:
    """Trace + compile the jitted callable(s) for one (program,
    shape-class, fabric) before real traffic arrives.

    Runs one throwaway launch — jit compiles on first execution, so the
    throwaway run IS the warm-up — and returns the cache keys it
    populated (empty tuple = that shape class was already warm). Params
    named in ``prog.init_only`` (per-request roots and friends) are not
    part of the key, so a single pre-warm covers every later request in
    the same shape class.
    """
    before = set(_CACHE)
    run_program(prog, data, fabric, **kwargs)
    return tuple(k for k in _CACHE if k not in before)


# ---------------------------------------------------------------------------
# the one-round owner-routed scatter (stream programs; public API)
# ---------------------------------------------------------------------------

def dcra_scatter(dest, vals, n, fabric, *,
                 options: Optional[LaunchOptions] = None,
                 op="add", task: str = "T3"):
    """Owner-routed scatter-reduce: one NoC round.

    dest/vals: [E] sharded over the device axes (edge-parallel tasks);
    returns y [n] sharded the same way (cyclic owner layout: item i lives
    on device i % n_dev at local slot i // n_dev) plus the dropped-task
    count (queue overflow).

    ``options.pod_axis`` selects the hierarchical pod/portal two-stage
    path (paper §III-A): stage 1 aggregates at the per-pod portal over
    ``options.axis`` (tile-NoC), stage 2 crosses pods exactly once
    (die-NoC).

    Queue sizing resolves through ONE path — :class:`QueueConfig` — like
    everywhere else in the repo. ``options.queues`` names the per-``task``
    IQ directly; ``cap`` / ``capacity_factor`` are sugar for
    ``QueueConfig.from_cap`` / ``QueueConfig.from_factor`` (default factor
    1.5). Explicit capacities are honored exactly (flat path only — the
    DSE revalidation sweeps the IQ axis in queue entries, so rounding
    would validate a different capacity than the analytic model swept);
    factor-derived capacities keep the lane-aligned round8. Compiled
    kernels are cached by (shapes, fabric key, capacities, op).
    ``fabric`` is a :class:`~repro.core.fabric.Fabric` (raw meshes keep
    working through the warn-once shim, with the identical cache key —
    :meth:`~repro.core.fabric.Fabric.fabric_key`).

    ``round_mode`` has no effect here — a scatter is a single round, so
    lockstep and pipelined are the same shape (and share one cache
    entry).
    """
    opts = (options or LaunchOptions()).resolve()
    axis, pod_axis, queues = opts.axis, opts.pod_axis, opts.queues
    fab = as_fabric(fabric)
    n_dev = fab.n_devices
    e_local = dest.shape[0] // n_dev
    n_local = -(-n // n_dev)
    if queues is None:
        queues = (QueueConfig.from_cap(opts.cap, task)
                  if opts.cap is not None
                  else QueueConfig.from_factor(
                      1.5 if opts.capacity_factor is None
                      else opts.capacity_factor, task))
    caps, pods = resolve_caps(fab, queues, task, e_local, axis, pod_axis)

    key = ("scatter", op, n_local, n_dev, axis, pod_axis, pods, caps,
           fab.fabric_key(), int(dest.shape[0]))
    fn = _cached(key, lambda: _build_scatter_fn(
        fab.mesh, axis, pod_axis, pods, n_dev, n_local, caps, op))
    spec = P((pod_axis, axis)) if pod_axis else P(axis)
    return fn(_to_global(fab, spec, dest), _to_global(fab, spec, vals))


def _build_scatter_fn(mesh, axis, pod_axis, pods,  # noqa: PLR0917
                      n_dev, n_local, caps, op):
    spec = P((pod_axis, axis)) if pod_axis else P(axis)

    if pod_axis is None:
        (cap,) = caps

        def kernel(dest_b, vals_b):
            CACHE_STATS["kernel_traces"] += 1
            valid = dest_b >= 0                    # padding -> no task
            dest_c = jnp.maximum(dest_b, 0)
            recv_slot, recv_val, n_drop = owner_route(
                vals_b, dest_c // n_dev, dest_c % n_dev, valid,
                n_dev, cap, axis)
            y = reduce_received(recv_slot, recv_val, n_local, op)
            return y, jax.lax.psum(n_drop, axis)
    else:
        n_intra, n_pods = pods
        cap1, cap2 = caps

        def kernel(dest_b, vals_b):
            CACHE_STATS["kernel_traces"] += 1
            valid = dest_b >= 0
            dest_c = jnp.maximum(dest_b, 0)
            recv_slot, recv_val, n_drop = owner_route_hier(
                vals_b, dest_c // n_dev, dest_c % n_dev, valid,
                n_intra, axis, n_pods, pod_axis, cap1, cap2)
            y = reduce_received(recv_slot, recv_val, n_local, op)
            return y, jax.lax.psum(n_drop, (pod_axis, axis))

    return jax.jit(shard_map_unchecked(kernel, mesh=mesh,
                                       in_specs=(spec, spec),
                                       out_specs=(spec, P())))


# ---------------------------------------------------------------------------
# the runtime
# ---------------------------------------------------------------------------

class ProgramLaunch:
    """One in-flight graph-program launch — a *device future*.

    JAX dispatch is asynchronous: the jitted shard_map call returns as
    soon as the computation is enqueued, with the output ``jax.Array``\\ s
    still materializing on device. :func:`launch_program` hands those
    raw outputs back wrapped in this object instead of blocking on host
    readback, so a caller (the serving tier's inflight window) can form
    and launch the NEXT batch while this one computes.

    * :meth:`is_ready` — non-blocking poll: have all output buffers
      committed? (``jax.Array.is_ready`` where available; conservatively
      ``True`` otherwise, so harvesting degrades to blocking.)
    * :meth:`block` — wait for completion without transferring; runtime
      errors of the computation surface here (and only poison THIS
      launch — the caller fails its riders, not the window).
    * :meth:`result` — block + host transfer + owner-layout unpack:
      exactly the ``(state_arrays, AppStats)`` the synchronous
      :func:`run_program` returns, bit-identical.

    ``launch`` is the launch's ordinal in this process. Every host span of
    the launch (``dcra.graph.pack`` / ``.upload`` / ``.dispatch`` in
    :func:`launch_program`, ``dcra.graph.wait`` / ``.transfer`` here)
    carries it as ``launch=<n>``, so a profile pairs the spans of one
    launch when the serving tier interleaves several.
    """

    def __init__(self, fab: Fabric, outs, n: int,  # noqa: PLR0917
                 n_dev: int, n_states: int, launch: int):
        self._fab, self._outs = fab, outs
        self._n, self._n_dev, self._n_states = n, n_dev, n_states
        self.launch = launch
        self._result = None

    def is_ready(self) -> bool:
        """True once every output buffer is committed (non-blocking)."""
        if self._result is not None:
            return True
        for a in self._outs:
            ready = getattr(a, "is_ready", None)
            if ready is not None and not ready():
                return False
        return True

    def block(self) -> "ProgramLaunch":
        """Wait for the device computation (no host transfer yet)."""
        with TraceAnnotation("dcra.graph.wait", launch=self.launch):
            jax.block_until_ready(self._outs)
        return self

    def result(self):
        """``(state_arrays, AppStats)`` — blocks, transfers, unpacks.
        Idempotent: the materialized result is cached on first call."""
        if self._result is None:
            outs = self._outs
            with TraceAnnotation("dcra.graph.wait", launch=self.launch):
                jax.block_until_ready(outs)
            with TraceAnnotation("dcra.graph.transfer", launch=self.launch):
                states = outs[:self._n_states]
                r, msgs, drops = outs[self._n_states:]
                stats = _collect_stats(r, msgs, drops)
                states_np = tuple(
                    np.asarray(from_owner_layout(_host_gather(self._fab, s),
                                                 self._n, self._n_dev),
                               np.float64)
                    for s in states)
            self._result = (states_np, stats)
            self._outs = None                 # release device buffers
        return self._result


def launch_program(prog: TaskProgram, data, fabric, *,
                   options: Optional[LaunchOptions] = None,
                   params: Optional[Mapping] = None,
                   max_rounds: Optional[int] = None,
                   donate_states: bool = False) -> ProgramLaunch:
    """Launch a *graph* :class:`TaskProgram` without blocking on host
    readback: returns a :class:`ProgramLaunch` device future.

    The compile-cache key, admission behaviour and results are identical
    to :func:`run_program` (which is now a thin ``launch + .result()``)
    — the only difference is WHEN the host waits. Stream
    (``mode="single"``) programs have no launch future (their scatter
    already returns sharded arrays); asking for one is an error.

    ``donate_states=True`` threads ``donate_argnums`` through the jitted
    shard_map call for the packed state buffers: the input tenant-column
    state array of each launch is donated to its same-shape output, so a
    retired batch's buffer is recycled instead of allocating a fresh
    output per launch (the serving tier's
    ``ServeOptions(donate_buffers=True)``). Donation changes lowering,
    so the flag joins the compile-cache key — but ONLY when set: default
    launches keep byte-identical cache keys.
    """
    if prog.mode == "single":
        raise ValueError("launch_program handles graph programs only — "
                         "stream programs return sharded arrays from "
                         "dcra_scatter already; use run_program")
    opts = (options or LaunchOptions()).resolve()
    return _launch_graph(prog, data, as_fabric(fabric), opts,
                         dict(params or {}), max_rounds,
                         donate_states=donate_states)


def run_program(prog: TaskProgram, data, fabric, *,
                options: Optional[LaunchOptions] = None,
                params: Optional[Mapping] = None,
                max_rounds: Optional[int] = None,
                dataset=None, donate_states: bool = False):
    """Execute a :class:`TaskProgram` on ``fabric``.

    Graph programs return ``(state_arrays, AppStats)`` — each state array
    unpacked to global order as float64; stream programs return
    ``(y_global, AppStats)`` with a single round. ``fabric`` is a
    :class:`~repro.core.fabric.Fabric` (single-process, fake-device rig
    or multi-process ``jax.distributed`` — on a multi-process fabric the
    packed inputs are laid out globally and the unpacked states gathered
    back, same numbers); raw meshes keep working through the warn-once
    shim with the identical compile-cache key. ``dataset`` overrides
    what ``config="auto"`` signatures (defaults to ``data``).

    ``options=`` takes a :class:`LaunchOptions` holding every launch
    setting. ``round_mode="pipelined"`` selects the double-buffered round
    shape on more than one device (see :func:`_build_graph_fn`) —
    bit-identical results and per-round stats, fewer collectives; one
    device runs one local round in either mode. Graph programs dispatch
    through :func:`launch_program` and block on its
    :meth:`ProgramLaunch.result` — the asynchronous serving tier skips
    only that final wait, never the launch path itself.
    """
    opts = (options or LaunchOptions()).resolve()
    axis, pod_axis, queues = opts.axis, opts.pod_axis, opts.queues
    params = dict(params or {})
    lc = resolve_launch(opts.config, data if dataset is None else dataset,
                        prog.name, opts.objective)
    fab = as_fabric(fabric)
    n_dev = fab.n_devices

    if prog.mode == "single":
        dest, vals, n_items = prog.stream(data, params, n_dev, opts.seed)
        if lc is not None:
            pod_axis = (pod_axis if pod_axis is not None
                        else lc.pod_axis_for(fab))
            queues = lc.device_queues(n_dev, len(dest) // n_dev,
                                      pod=pod_axis is not None)
        if queues is None:
            queues = _resolve_queues(prog, None, opts.cap,
                                     opts.capacity_factor)
        if (prog.local_reduce is not None and n_dev == 1
                and pod_axis is None):
            e_local = len(dest)
            rcap = resolve_flat_cap(queues, prog.task, e_local, n_dev)
            if rcap >= e_local:    # no task can drop -> bit-identical
                y = prog.local_reduce(data, dest, vals, n_items)
                if y is not None:
                    stats = AppStats(
                        rounds=1,
                        messages=np.array([int((dest >= 0).sum())],
                                          np.int64),
                        drops=np.array([0], np.int64))
                    return y, stats
        y_sh, dropped = dcra_scatter(
            jnp.asarray(dest), jnp.asarray(vals), n_items, fab,
            options=LaunchOptions(axis=axis, pod_axis=pod_axis,
                                  queues=queues),
            op=prog.reduce_op, task=prog.task)
        stats = AppStats(rounds=1,
                         messages=np.array([int((dest >= 0).sum())],
                                           np.int64),
                         drops=np.array([int(dropped)], np.int64))
        return from_owner_layout(_host_gather(fab, y_sh), n_items,
                                 n_dev), stats

    # ---- graph program: async dispatch + immediate harvest ---------------
    return _launch_graph(prog, data, fab, opts, params, max_rounds,
                         dataset=dataset,
                         donate_states=donate_states).result()


#: ordinals of this process's graph launches (see :class:`ProgramLaunch`)
_LAUNCHES = itertools.count()


def _resolve_graph(prog: TaskProgram, g, fab: Fabric, opts: LaunchOptions,
                   *, dataset=None):
    """What a graph launch on ``fab`` resolves before it packs any state:
    ``(packed_graph, pod_axis, caps, pods, owner_capped)``, the last true
    where the graph's owner bound lowered the flat cap
    (:func:`_owner_caps`). The one resolution both :func:`_launch_graph`
    and :func:`wire_plan` read."""
    pod_axis, queues = opts.pod_axis, opts.queues
    n_dev = fab.n_devices
    lc = resolve_launch(opts.config, g if dataset is None else dataset,
                        prog.name, opts.objective)
    pg = packed_graph(g, n_dev, undirected=prog.undirected, seed=opts.seed)
    if lc is not None:
        pod_axis = (pod_axis if pod_axis is not None
                    else lc.pod_axis_for(fab))
        queues = lc.device_queues(n_dev, pg.E_max,
                                  pod=pod_axis is not None)
    if queues is None:
        queues = _resolve_queues(prog, None, opts.cap, opts.capacity_factor)
    caps, pods = resolve_caps(fab, queues, prog.task, pg.E_max, opts.axis,
                              pod_axis, clamp=True)
    capped = _owner_caps(pg, caps, pods)
    return pg, pod_axis, capped, pods, capped != caps


def _folds_local(n_dev: int, pod_axis) -> bool:
    """Whether a graph round runs on one shard with a local communication
    edge (see :func:`_build_graph_fn`): no bucket, wire or collective."""
    return n_dev == 1 and pod_axis is None


class WirePlan(NamedTuple):
    """The wire one graph launch ships each round, per chip.

    ``slots_per_round[i]`` is the row count of the i-th all_to_all a
    round issues on one chip (stage 1, then stage 2 on a pod fabric):
    every destination bucket's ``cap`` slots, full or not, plus the
    pipelined shape's signal row per bucket. ``words_per_slot[i]`` is
    :func:`~repro.core.routing.pack_wire`'s int32 columns for that
    stage. ``bytes_per_round`` sums rows times words times 4 over the
    stages. A one-device launch folds its round and ships nothing: no
    stages, 0 bytes.
    """
    n_dev: int
    caps: Tuple[int, ...]
    slots_per_round: Tuple[int, ...]
    words_per_slot: Tuple[int, ...]
    bytes_per_round: int


def wire_plan(prog: TaskProgram, data, fabric,
              options: Optional[LaunchOptions] = None) -> WirePlan:
    """The :class:`WirePlan` of ``launch_program(prog, data, fabric,
    options=options)``, from the same resolution the launch makes (so it
    packs the graph on first use, as the launch would) and the wire
    layout of :func:`~repro.core.routing.route_wire`."""
    opts = (options or LaunchOptions()).resolve()
    fab = as_fabric(fabric)
    n_dev = fab.n_devices
    _, pod_axis, caps, pods, _ = _resolve_graph(prog, data, fab, opts)
    stages = () if _folds_local(n_dev, pod_axis) else route_wire(
        n_dev, caps, pods, signal=opts.round_mode == "pipelined")
    return WirePlan(n_dev=n_dev, caps=tuple(caps),
                    slots_per_round=tuple(r for r, _ in stages),
                    words_per_slot=tuple(w for _, w in stages),
                    bytes_per_round=sum(4 * r * w for r, w in stages))


def _launch_graph(prog: TaskProgram, g, fab: Fabric,  # noqa: PLR0917
                  opts: LaunchOptions, params, max_rounds,
                  dataset=None, donate_states: bool = False
                  ) -> ProgramLaunch:
    """The graph-program launch path shared by :func:`run_program` and
    :func:`launch_program`: resolve, pack, hit the compile cache, and
    dispatch — returning the :class:`ProgramLaunch` device future
    *without* waiting on the result.

    The graph's edges are resident: the first launch on a graph packs
    them (:func:`packed_graph`) and places them on the fabric, and every
    later launch on the same graph object, seed and fabric hands the jitted
    call the device arrays already there. What each launch still does is
    the V-sized work: the initial state, packed and uploaded.

    The host work is three spans: ``dcra.graph.pack`` (launch resolution,
    the packed-graph lookup, which packs the edges on a miss, and state
    packing), ``dcra.graph.upload`` (the edges' placement on a miss, the
    states onto the fabric) and ``dcra.graph.dispatch`` (compile-cache
    lookup and the jitted call, which returns once the computation is
    enqueued)."""
    axis, round_mode = opts.axis, opts.round_mode
    launch = next(_LAUNCHES)
    n_dev = fab.n_devices
    n = g.n
    with TraceAnnotation("dcra.graph.pack", launch=launch):
        pg, pod_axis, caps, pods, owner_capped = _resolve_graph(
            prog, g, fab, opts, dataset=dataset)
        n_local, E_max = pg.n_local, pg.E_max
        states0, fills = prog.init(g, params)
        packed = tuple(np.asarray(_owner_pack_np(s, n_dev, f)[0],
                                  np.float32)
                       for s, f in zip(states0, fills))
    if prog.mode == "fixed":
        rounds = int(params["iters"])
    else:
        rounds = int(max_rounds if max_rounds is not None
                     else prog.max_rounds)

    # init-only params (per-request roots etc.) feed the packed state
    # arrays, never the traced rules — keep them out of the key and out
    # of the kernel's Ctx so serving-style request streams hit the cache
    kparams = {k: v for k, v in params.items() if k not in prog.init_only}
    if rounds == 0:
        round_mode = "lockstep"          # no rounds, nothing to overlap
    key = (prog, n, n_dev, n_local, E_max, axis, pod_axis, pods, caps,
           rounds, round_mode, len(packed),
           tuple(sorted(kparams.items())), fab.fabric_key())
    if donate_states:
        # donation changes lowering (input/output buffer aliasing), so it
        # joins the key — but ONLY when set, keeping default launches'
        # cache keys byte-identical to every prior release
        key = key + ("donate",)
    spec = P((pod_axis, axis)) if pod_axis else P(axis)
    with TraceAnnotation("dcra.graph.upload", launch=launch):
        args = [*pg.edges_on(fab, spec),
                *(_to_global(fab, spec, a) for a in packed)]

    def build():
        if owner_capped:
            CACHE_STATS["owner_capped_builds"] += 1
        return _build_graph_fn(
            prog, fab.mesh, axis, pod_axis, pods, n_dev, n_local, n, caps,
            kparams, rounds, len(packed), round_mode=round_mode,
            donate_states=donate_states)

    with TraceAnnotation("dcra.graph.dispatch", launch=launch):
        fn = _cached(key, build)
        out = fn(*args)
    return ProgramLaunch(fab, tuple(out), n, n_dev, len(packed), launch)


def _build_graph_fn(prog, mesh, axis, pod_axis, pods,  # noqa: PLR0917
                    n_dev, n_local, n,
                    caps, params, rounds, n_states,
                    round_mode="lockstep", donate_states=False):
    """Build the jitted shard_map callable for one graph-program shape.

    Two execution shapes, selected by ``round_mode`` (bit-identical
    results and per-round stats — differentially tested in
    tests/test_pipeline.py):

    * ``"lockstep"`` — the classic round: payload -> bucket -> fused
      all_to_all -> receive-reduce -> update, plus per-round scalar psums
      for the message count, the drop count and (while mode) the
      convergence predicate: 4 collectives per round.
    * ``"pipelined"`` — the double-buffered round: the collective for
      round k is launched at the tail of loop iteration k-1 and its
      receive-reduce is folded into the head of iteration k, so round
      k+1's payload + bucket-rank run while round k's wire buffer is the
      loop carry. Message/drop counters stay shard-local int32 streams
      committed per round and are psum'd ONCE after the loop (integer
      sums — order-free, so the stats are bit-identical), and the
      while-mode convergence count rides the collective itself as one
      extra broadcast row per destination bucket
      (:func:`~repro.core.routing._a2a_with_signal`): 1 collective per
      round. A converged launch costs one ghost iteration whose commits
      are all gated off (``is_real``), exactly reproducing lockstep's
      "round 0 always executes" initial ``changed=True``.

    A one-device flat launch (``n_dev == 1``, no ``pod_axis``) has a
    *local* communication edge whatever ``round_mode`` says, so it runs
    neither shape's bucket and collective: the receive-reduce is folded
    into admission (:func:`~repro.core.routing.local_route`) — rank,
    capacity test, then the segment reduce straight off the edge stream.
    The kept set is ``bucket``'s, and with one bucket array order is
    bucket order, so every reduce op (``add`` included) gives the
    two-pass result and drop count; both round modes run the same
    lockstep loop, with nothing to overlap. ``CACHE_STATS
    ["local_fold_builds"]`` counts the callables built this way.

    In every shape a round's device work runs under four scopes, so a
    device profile splits the round by phase: ``dcra.graph.payload``
    (active edges and their values), ``dcra.graph.route`` (bucket and
    collective, or the local rank), ``dcra.graph.reduce``
    (receive-reduce) and ``dcra.graph.update`` (state update, counter
    psums and per-round commits). On more than one device every scalar
    psum (``gsum``: the message, drop and convergence counts, the
    pipelined shape's post-loop sums, and a rule's own, such as
    PageRank's dangling mass) runs under ``dcra.graph.sync`` inside the
    scope it is called from; a folded round's psums over one device have
    no scope of their own.
    """
    spec = P((pod_axis, axis)) if pod_axis else P(axis)
    axes = (pod_axis, axis) if pod_axis else axis

    fold_local = _folds_local(n_dev, pod_axis)

    def gsum(x):
        if fold_local:
            return jax.lax.psum(x, axes)
        with jax.named_scope("dcra.graph.sync"):
            return jax.lax.psum(x, axes)

    ctx = Ctx(xp=jnp, n=n, n_dev=n_dev, params=params, gsum=gsum)
    pipelined = round_mode == "pipelined" and not fold_local
    if fold_local:
        CACHE_STATS["local_fold_builds"] += 1

    def kernel(src_slot_b, dst_b, w_b, *state_b):
        CACHE_STATS["kernel_traces"] += 1
        owner = jnp.maximum(dst_b, 0) % n_dev
        slot = jnp.maximum(dst_b, 0) // n_dev
        evalid = dst_b >= 0

        def payload(state, frontier):
            """The active edges and the value each one sends."""
            with jax.named_scope("dcra.graph.payload"):
                active = (frontier[src_slot_b] & evalid
                          if prog.active == "frontier" else evalid)
                vals = prog.payload(ctx, state, src_slot_b,
                                    w_b).astype(jnp.float32)
            return active, vals

        def do_round(state, frontier):
            active, vals = payload(state, frontier)
            with jax.named_scope("dcra.graph.route"):
                if fold_local:
                    recv_slot, recv_val, nd = local_route(
                        vals, slot, owner, active, n_dev, caps[0])
                elif pod_axis is None:
                    recv_slot, recv_val, nd = owner_route(
                        vals, slot, owner, active, n_dev, caps[0], axis)
                else:
                    recv_slot, recv_val, nd = owner_route_hier(
                        vals, slot, owner, active, pods[0], axis, pods[1],
                        pod_axis, caps[0], caps[1])
            with jax.named_scope("dcra.graph.reduce"):
                upd = reduce_received(recv_slot, recv_val, n_local,
                                      prog.reduce_op)
            with jax.named_scope("dcra.graph.update"):
                state2, frontier2 = prog.update(ctx, state, frontier, upd)
                return (state2, frontier2,
                        gsum(jnp.sum(active.astype(jnp.int32))),
                        gsum(nd.astype(jnp.int32)))

        # -- pipelined produce/consume halves --------------------------------
        meta_box = []                 # static wire meta (same every round)

        def produce(state, frontier):
            """Round tail: payload + bucket + LAUNCH the collective.
            Stats stay shard-local; the local frontier count rides the
            wire as the convergence signal."""
            active, vals = payload(state, frontier)
            with jax.named_scope("dcra.graph.update"):
                m_loc = jnp.sum(active.astype(jnp.int32))
                fcnt = jnp.sum(frontier.astype(jnp.int32))
            with jax.named_scope("dcra.graph.route"):
                if pod_axis is None:
                    recv, meta, nd_loc, gcnt = owner_route_start(
                        vals, slot, owner, active, n_dev, caps[0], axis,
                        fcnt)
                else:
                    recv, meta, nd_loc, gcnt = owner_route_hier_start(
                        vals, slot, owner, active, pods[0], axis, pods[1],
                        pod_axis, caps[0], caps[1], fcnt)
            if not meta_box:
                meta_box.append(meta)
            return recv, m_loc, nd_loc, gcnt

        def consume(recv):
            """Round head: receive-reduce folded into the carried
            communication edge."""
            with jax.named_scope("dcra.graph.route"):
                recv_slot, recv_val = owner_route_finish(recv, meta_box[0])
            with jax.named_scope("dcra.graph.reduce"):
                return reduce_received(recv_slot, recv_val, n_local,
                                       prog.reduce_op)

        zeros = jnp.zeros((rounds,), jnp.int32)
        frontier0 = prog.frontier0(ctx, state_b)

        if prog.mode == "while" and pipelined:
            recv0, m0, nd0, g0 = produce(state_b, frontier0)

            def cond(s):
                r, running = s[6], s[9]
                return running & (r < rounds)

            def body(s):
                (state, frontier, recv, m_pend, nd_pend, gcnt, r, msgs,
                 drops, _run) = s
                upd = consume(recv)
                with jax.named_scope("dcra.graph.update"):
                    # gcnt is the global pre-round frontier count (summed
                    # across both hier stages), identical on every shard —
                    # round 0 always executes, like lockstep's changed=True
                    is_real = (gcnt > 0) | (r == 0)
                    state2, frontier2 = prog.update(ctx, state, frontier,
                                                    upd)
                    state_n = tuple(jnp.where(is_real, a, b)
                                    for a, b in zip(state2, state))
                    frontier_n = jnp.where(is_real, frontier2, frontier)
                    msgs_n = jnp.where(is_real, msgs.at[r].set(m_pend), msgs)
                    drops_n = jnp.where(is_real, drops.at[r].set(nd_pend),
                                        drops)
                    r_n = r + is_real.astype(jnp.int32)
                recv_n, m_n, nd_n, g_n = produce(state_n, frontier_n)
                return (state_n, frontier_n, recv_n, m_n, nd_n, g_n, r_n,
                        msgs_n, drops_n, is_real)

            out = jax.lax.while_loop(
                cond, body, (state_b, frontier0, recv0, m0, nd0, g0,
                             jnp.int32(0), zeros, zeros, jnp.bool_(True)))
            state, r = out[0], out[6]
            with jax.named_scope("dcra.graph.update"):
                msgs, drops = gsum(out[7]), gsum(out[8])
        elif prog.mode == "while":                 # lockstep / fold_local
            def cond(s):
                _, _, r, _, _, changed = s
                return changed & (r < rounds)

            def body(s):
                state, frontier, r, msgs, drops, _ = s
                state2, frontier2, m, nd = do_round(state, frontier)
                with jax.named_scope("dcra.graph.update"):
                    changed = gsum(jnp.sum(frontier2.astype(jnp.int32))) > 0
                    return (state2, frontier2, r + 1, msgs.at[r].set(m),
                            drops.at[r].set(nd), changed)

            state, _, r, msgs, drops, _ = jax.lax.while_loop(
                cond, body, (state_b, frontier0, jnp.int32(0), zeros,
                             zeros, jnp.bool_(True)))
        elif pipelined:                            # "fixed", double-buffered
            recv0, m0, nd0, _g0 = produce(state_b, frontier0)

            def body(i, s):
                state, frontier, recv, m_pend, nd_pend, msgs, drops = s
                upd = consume(recv)
                with jax.named_scope("dcra.graph.update"):
                    state2, frontier2 = prog.update(ctx, state, frontier,
                                                    upd)
                    msgs, drops = (msgs.at[i].set(m_pend),
                                   drops.at[i].set(nd_pend))
                recv_n, m_n, nd_n, _g = produce(state2, frontier2)
                return (state2, frontier2, recv_n, m_n, nd_n, msgs, drops)

            # rounds-1 full iterations, then drain the last in-flight
            # round without launching a trailing (wasted) collective
            s = jax.lax.fori_loop(0, rounds - 1, body,
                                  (state_b, frontier0, recv0, m0, nd0,
                                   zeros, zeros))
            state, frontier, recv, m_pend, nd_pend, msgs, drops = s
            upd = consume(recv)
            with jax.named_scope("dcra.graph.update"):
                state, _f = prog.update(ctx, state, frontier, upd)
                msgs = gsum(msgs.at[rounds - 1].set(m_pend))
                drops = gsum(drops.at[rounds - 1].set(nd_pend))
            r = jnp.int32(rounds)
        else:                                      # "fixed" lockstep/fold
            def body(i, s):
                state, frontier, msgs, drops = s
                state2, frontier2, m, nd = do_round(state, frontier)
                with jax.named_scope("dcra.graph.update"):
                    return (state2, frontier2, msgs.at[i].set(m),
                            drops.at[i].set(nd))

            state, _, msgs, drops = jax.lax.fori_loop(
                0, rounds, body, (state_b, frontier0, zeros, zeros))
            r = jnp.int32(rounds)
        return (*state, r, msgs, drops)

    in_specs = (spec, spec, spec) + (spec,) * n_states
    out_specs = (spec,) * n_states + (P(), P(), P())
    # donation aliases each packed state input onto the matching state
    # output: a retired batch's tenant-column buffer is handed straight
    # to the next launch of the same shape class instead of allocating
    donate = tuple(range(3, 3 + n_states)) if donate_states else ()
    return jax.jit(shard_map_unchecked(kernel, mesh=mesh,
                                       in_specs=in_specs,
                                       out_specs=out_specs),
                   donate_argnums=donate)


# ---------------------------------------------------------------------------
# the analytic twin: host mirror + TaskEngine replay
# ---------------------------------------------------------------------------

def _bucket_positions(chan, active):
    """Stable per-channel cumcount of the active tasks, in array order —
    the admission order of the shard_map ``bucket``. -1 where inactive."""
    pos = np.full(len(chan), -1, np.int64)
    idx = np.flatnonzero(active)
    if not len(idx):
        return pos
    k = chan[idx]
    order = np.argsort(k, kind="stable")
    ks = k[order]
    starts = np.r_[0, np.flatnonzero(ks[1:] != ks[:-1]) + 1]
    sizes = np.diff(np.r_[starts, len(ks)])
    p = np.arange(len(ks)) - np.repeat(starts, sizes)
    out = np.empty(len(ks), np.int64)
    out[order] = p
    pos[idx] = out
    return pos


def _flat_keep(dev_of, owner, active, cap, n_dev):  # noqa: PLR0917
    pos = _bucket_positions(dev_of * n_dev + owner, active)
    keep = active & (pos < cap)
    return keep, int(active.sum() - keep.sum())


def _hier_keep(dev_of, owner, active, caps, pods):  # noqa: PLR0917
    """Two-stage pod/portal keep rule (mirrors ``owner_route_hier``):
    stage 1 admits per (sender, dest-intra-coordinate) channel at cap1;
    stage 2 admits at the portal per dest pod at cap2, in the receive
    order the tiled all_to_all produces (sender intra rank, then stage-1
    slot)."""
    n_intra, n_pods = pods
    cap1, cap2 = caps
    e_coord = owner % n_intra
    p_coord = owner // n_intra
    pos1 = _bucket_positions(dev_of * n_intra + e_coord, active)
    keep1 = active & (pos1 < cap1)
    drop1 = int(active.sum() - keep1.sum())
    portal = (dev_of // n_intra) * n_intra + e_coord
    idx = np.flatnonzero(keep1)
    arr = idx[np.lexsort((pos1[idx], dev_of[idx] % n_intra, portal[idx]))]
    chan2 = (portal * n_pods + p_coord)[arr]
    pos2 = _bucket_positions(chan2, np.ones(len(arr), bool))
    keep = np.zeros(len(active), bool)
    keep[arr[pos2 < cap2]] = True
    drop2 = int(len(arr) - keep.sum())
    return keep, drop1 + drop2


def program_rounds(prog: TaskProgram, g, n_dev, caps,  # noqa: PLR0917
                   params=None, seed=0,
                   pods=None, max_rounds=None, packed=None):
    """Host mirror of :func:`run_program`'s round loop for a graph
    program: yields, per executable round, the routed task stream
    ``(src_global, dst_global, n_drop)`` — *all* active tasks, with the
    drop count of the first-``cap``-per-channel keep rule — while
    evolving vertex state with kept-only updates, exactly as the
    shard_map path does. Deterministic: reads the executable's own
    :class:`PackedGraph` (and so its admission order); ``packed`` passes
    one already looked up.
    """
    params = dict(params or {})
    n = g.n
    if packed is None:
        packed = packed_graph(g, n_dev, undirected=prog.undirected,
                              seed=seed)
    n_local, src_slot, dst, w, E_max = (packed.n_local, packed.src_slot,
                                        packed.dst, packed.w, packed.E_max)
    dev_of = np.repeat(np.arange(n_dev), E_max)
    evalid = dst >= 0
    dstl = dst.astype(np.int64)
    owner = np.where(evalid, dstl % n_dev, 0)
    src_global = src_slot.astype(np.int64) * n_dev + dev_of
    # the kernel indexes shard-local state with src_slot; the mirror's
    # state is the full device-major packed array, so offset by device
    psrc = dev_of * n_local + src_slot

    ctx = Ctx(xp=np, n=n, n_dev=n_dev, params=params,
              gsum=lambda x: x)
    states0, fills = prog.init(g, params)
    state = tuple(np.asarray(_owner_pack_np(s, n_dev, f)[0], np.float32)
                  for s, f in zip(states0, fills))
    frontier = np.asarray(prog.frontier0(ctx, state), bool)
    if prog.mode == "fixed":
        rounds = int(params["iters"])
    else:
        rounds = int(max_rounds if max_rounds is not None
                     else prog.max_rounds)

    changed, r = True, 0
    while r < rounds and (prog.mode == "fixed" or changed):
        active = (frontier[psrc] & evalid
                  if prog.active == "frontier" else evalid.copy())
        vals = np.asarray(prog.payload(ctx, state, psrc, w), np.float32)
        if pods is None:
            keep, n_drop = _flat_keep(dev_of, owner, active, caps[0], n_dev)
        else:
            keep, n_drop = _hier_keep(dev_of, owner, active, caps, pods)
        kd = dstl[keep]
        kidx = (kd % n_dev) * n_local + kd // n_dev
        if prog.reduce_op == "min":
            upd = np.full(n_dev * n_local, np.inf, np.float32)
            np.minimum.at(upd, kidx, vals[keep])
        else:
            upd = np.zeros(n_dev * n_local, np.float32)
            np.add.at(upd, kidx, vals[keep])
        yield src_global[active], dstl[active], n_drop
        state, frontier = prog.update(ctx, state, frontier, upd)
        frontier = np.asarray(frontier, bool)
        changed = bool(frontier.any())
        r += 1


def program_app_stats(prog: TaskProgram, data, n_dev, *,
                      queues: Optional[QueueConfig] = None,
                      cap: Optional[int] = None,
                      capacity_factor: Optional[float] = None,
                      params=None, seed=0,
                      pods: Optional[Tuple[int, int]] = None,
                      max_rounds=None) -> AppStats:
    """The analytic twin of one program launch.

    Generates the program's task stream (:func:`program_rounds` /
    ``prog.stream``) and replays each flat round through
    ``TaskEngine.route`` on a ``TileGrid(1, n_dev)`` with the capacity
    resolved through the SAME :class:`QueueConfig` path the executable
    uses — the per-(source shard -> owner) channel structure is
    identical, so per-round message/drop counts must match the
    executable's :class:`AppStats` exactly. The pod/portal path is
    counted by the two-stage channel mirror (``TaskEngine`` models a
    single flat channel set).
    """
    params = dict(params or {})
    queues = _resolve_queues(prog, queues, cap, capacity_factor)

    if prog.mode == "single":
        dest, _, n_items = prog.stream(data, params, n_dev, seed)
        e_local = len(dest) // n_dev
        dev_of = np.repeat(np.arange(n_dev), e_local)
        active = dest >= 0
        if pods is None:
            rcap = resolve_flat_cap(queues, prog.task, e_local, n_dev)
            engine = TaskEngine(EngineConfig(
                grid=TileGrid(1, n_dev),
                queues=QueueConfig(default_iq=rcap)), n_items)
            rs = engine.route(prog.task, src_idx=dev_of[active],
                              dst_idx=dest[active].astype(np.int64))
            return AppStats(rounds=1,
                            messages=np.array([rs.tasks_total], np.int64),
                            drops=np.array([rs.drops], np.int64))
        caps = resolve_hier_caps(queues, prog.task, e_local, *pods)
        owner = np.where(active, dest.astype(np.int64) % n_dev, 0)
        _, n_drop = _hier_keep(dev_of, owner, active, caps, pods)
        return AppStats(rounds=1,
                        messages=np.array([int(active.sum())], np.int64),
                        drops=np.array([n_drop], np.int64))

    # graph program: mirror the rounds, replay flat rounds through route()
    packed = packed_graph(data, n_dev, undirected=prog.undirected,
                          seed=seed)
    caps = _owner_caps(packed, _graph_caps(queues, prog.task, packed.E_max,
                                           n_dev, pods), pods)
    msgs, drops = [], []
    engine = None
    if pods is None:
        engine = TaskEngine(EngineConfig(
            grid=TileGrid(1, n_dev),
            queues=QueueConfig(default_iq=caps[0])), data.n)
    for src, dst, n_drop in program_rounds(prog, data, n_dev, caps,
                                           params=params, seed=seed,
                                           pods=pods, max_rounds=max_rounds,
                                           packed=packed):
        if engine is not None:
            rs = engine.route(prog.task, src_idx=src, dst_idx=dst)
            assert rs.drops == n_drop, (rs.drops, n_drop)  # model coherence
            msgs.append(rs.tasks_total)
            drops.append(rs.drops)
        else:
            msgs.append(len(dst))
            drops.append(n_drop)
    return AppStats(rounds=len(msgs),
                    messages=np.asarray(msgs, np.int64),
                    drops=np.asarray(drops, np.int64))
