"""Owner-routed NoC collective layer — THE shared DCRA primitive.

Everything DCRA routes — MoE tokens to expert-owning tiles
(:mod:`repro.core.dispatch`) and graph/sparse update tasks to
vertex-owning tiles (:mod:`repro.sparse.jax_apps`) — is the same motion:

  1. *bucket*: tasks are grouped by destination shard into capacity-bounded
     buckets (the paper's input queue; overflow is dropped and counted),
     ranked within their bucket by the one routing rank,
     :func:`repro.kernels.route.bucket_rank`, and scattered into slot
     order;
  2. *deliver*: ONE ``all_to_all`` per NoC round carries a *fused payload* —
     value columns are bitcast (bytes reinterpreted, never converted) to
     int32 and packed next to the int32 metadata columns, so index+value
     travel in a single collective instead of two;
  3. optionally *hierarchical*: when shards span pods, stage 1 routes over
     the intra-pod axis to the destination's "portal" (the device in the
     sender's pod sharing the destination's intra-pod coordinate), stage 2
     hops once over the pod axis (die-NoC) — the paper's §III-A two-level
     torus.

All functions here are **per-shard**: they are meant to be called *inside*
a ``shard_map`` kernel (possibly inside a ``lax.while_loop`` for iterative
apps), so callers control layout, reduction, and the return path.

The three steps run under device scopes (``jax.named_scope``), nested in
the caller's: ``dcra.route.rank`` (position within the destination
bucket), ``dcra.route.scatter`` (tasks into slot order) and
``dcra.route.a2a`` (the collective). A profile then attributes each
device op to its step by the op's name stack.

Shard-id convention for the hierarchical path: global shard
``g = pod * n_intra + intra`` — pods are the slow axis, matching a mesh
declared as ``('pod', ..., intra_axis)``.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# Capacity helpers live with the queue-sizing source of truth; re-exported
# here because every routing call site thinks in lane-aligned bucket sizes.
from .queues import round8  # noqa: F401
# The rank of every bucketing is repro.kernels.route.bucket_rank (Mosaic
# on TPU, the same tiled algorithm in plain XLA off-TPU).
from ..kernels.route import bucket_rank


# ---------------------------------------------------------------------------
# per-round capacity resolution (shared by every routing call site)
# ---------------------------------------------------------------------------

def resolve_flat_cap(queues, task: str, e_local: int, n_shards: int,
                     clamp: bool = False) -> int:
    """One flat routing round's per-channel bucket capacity.

    Resolves through :meth:`QueueConfig.channel_cap` (the single IQ
    source of truth). ``None`` (unbounded) resolves to ``e_local`` — every
    local task fits its owner bucket. ``clamp=True`` additionally trims an
    explicit capacity at ``e_local``: a shard can never send more than its
    whole slice to one owner, so the clamp only shrinks the *allocation*
    (the receive buffer), never the admission behaviour — drop counts are
    identical either way, which is what keeps the analytic twin exact.
    """
    cap = queues.channel_cap(task, e_local, n_shards)
    if cap is None:
        cap = max(1, e_local)
    elif clamp:
        cap = min(int(cap), max(1, e_local))
    return max(1, int(cap))


def resolve_hier_caps(queues, task: str, e_local: int, n_intra: int,
                      n_pods: int) -> Tuple[int, int]:
    """Stage-1 (tile-NoC) / stage-2 (die-NoC portal) capacities for the
    pod/portal path. Stage 2 sizes from stage 1's worst-case egress
    (``n_intra * cap1`` tasks can land on one portal)."""
    cap1 = queues.channel_cap(task, e_local, n_intra)
    cap1 = max(1, e_local) if cap1 is None else int(cap1)
    cap2 = queues.channel_cap(task, n_intra * cap1, n_pods)
    cap2 = max(1, n_intra * cap1) if cap2 is None else int(cap2)
    return cap1, cap2


def resolve_caps(fabric, queues, task: str, e_local: int, axis: str,
                 pod_axis: Optional[str], *, clamp: bool = False
                 ) -> Tuple[Tuple[int, ...], Optional[Tuple[int, int]]]:
    """One launch's per-round capacities against a fabric: ``(caps, pods)``.

    Flat path (``pod_axis is None``): a 1-tuple cap over the fabric's
    whole device count, ``pods = None``. Pod/portal path: the 2-stage
    caps plus ``pods = (n_intra, n_pods)`` read off the fabric's axis
    sizes — the ONE place launches turn mesh axes into routing stage
    sizes (previously re-derived privately by ``dcra_scatter`` and the
    graph runtime). Explicit per-``task`` capacities are only defined for
    the flat path — the DSE revalidation honors them exactly, while the
    2-stage caps are relative. ``fabric`` is duck-typed (anything with
    ``axis_sizes`` / ``n_devices``, i.e. :class:`repro.core.fabric
    .Fabric`), so this layer stays import-free of the fabric module.
    """
    if queues.iq_sizes.get(task) is not None and pod_axis is not None:
        raise ValueError("explicit cap is only defined for the flat path")
    if pod_axis is None:
        return ((resolve_flat_cap(queues, task, e_local, fabric.n_devices,
                                  clamp=clamp),), None)
    sizes = fabric.axis_sizes
    pods = (sizes[axis], sizes[pod_axis])
    return resolve_hier_caps(queues, task, e_local, *pods), pods


# ---------------------------------------------------------------------------
# bucketing (the bounded IQ)
# ---------------------------------------------------------------------------

def positions_by_dest(dest, valid, n_buckets):
    """Stable position of each *valid* task within its destination bucket
    (invalid entries are unspecified — callers mask with ``valid``), by
    :func:`repro.kernels.route.bucket_rank`: O(N + S*tiles), elements
    streamed in tiles against per-destination running counts.
    """
    with jax.named_scope("dcra.route.rank"):
        return bucket_rank(dest, valid, n_buckets)


def slot_scatter(data, slot, valid, num_slots):
    """Scatter rows of ``data`` into slots (each slot receives <= 1 row)."""
    with jax.named_scope("dcra.route.scatter"):
        seg = jnp.where(valid, slot, num_slots)
        if data.ndim > 1:
            data = data * valid[:, None].astype(data.dtype)
        else:
            data = data * valid.astype(data.dtype)
        return jax.ops.segment_sum(data, seg,
                                   num_segments=num_slots + 1)[:num_slots]


def bucket(x_tasks, dest, valid, aux_ints, n_buckets, cap):
    """Capacity-bounded bucketing (the IQ). Returns (xb, ints, slot, n_drop).

    xb [n_buckets*cap, D]; ints: like aux_ints but slot-ordered (-1 = empty);
    also returns each task's slot (-1 if dropped) for building return maps.

    Admission keeps the first ``cap`` tasks per channel in array order,
    the rule the analytic twins mirror, so they stay exact.
    """
    pos = positions_by_dest(dest, valid, n_buckets)
    keep = valid & (pos < cap)
    slot = dest * cap + jnp.minimum(pos, cap - 1)
    total = n_buckets * cap
    xb = slot_scatter(x_tasks, slot, keep, total)
    ints = [slot_scatter((a + 1).astype(jnp.int32), slot, keep, total) - 1
            for a in aux_ints]
    task_slot = jnp.where(keep, slot, -1)
    n_drop = jnp.sum(valid & ~keep)
    return xb, ints, task_slot, n_drop


def gather_rows(table, ids):
    """rows = table[ids] with id -1 -> zero rows (one gather; no K-fold
    payload replication before bucketing)."""
    rows = table[jnp.maximum(ids, 0)]
    return rows * (ids >= 0)[:, None].astype(rows.dtype)


# ---------------------------------------------------------------------------
# the NoC round: one fused all_to_all
# ---------------------------------------------------------------------------

def noc_all_to_all(x, axis):
    """One NoC round over ``axis`` (tiled all_to_all on the leading dim)."""
    with jax.named_scope("dcra.route.a2a"):
        return jax.lax.all_to_all(x, axis, 0, 0, tiled=True)


def pack_wire(vals: Optional[jax.Array], int_cols: Sequence[jax.Array]
              ) -> Tuple[jax.Array, tuple]:
    """Pack value + int32 metadata columns into one int32 wire array.

    Values are *bitcast* to int32 — bytes reinterpreted, never converted.
    The wire is integer because the TPU compiler may lower a float
    concatenate as pad + ``maximum``, which rewrites every NaN bit pattern
    to the canonical NaN: an int such as the -1 "empty" sentinel, bitcast
    to f32, is such a pattern. Half-width payloads (bf16/f16) are packed
    two per wire lane (bitcast, not upcast), so fusing never inflates the
    wire bytes: the packed array has exactly ``ceil(D/2) + len(int_cols)``
    columns for a half payload, ``D + len(int_cols)`` otherwise. Returns
    ``(packed, meta)``; feed ``meta`` to :func:`unpack_wire` for the
    exact round-trip (tested in tests/test_routing.py).
    """
    if vals is None and not int_cols:
        raise ValueError("nothing to route")
    cols = []
    squeeze = False
    dtype = None
    d_vals = 0
    half = False
    if vals is not None:
        dtype = vals.dtype
        v2 = vals
        if v2.ndim == 1:
            v2, squeeze = v2[:, None], True
        d_vals = v2.shape[1]
        half = dtype.itemsize == 2
        if half:
            if d_vals % 2:
                v2 = jnp.concatenate([v2, jnp.zeros_like(v2[:, :1])], axis=1)
            v2 = v2.reshape(v2.shape[0], -1, 2)
        else:
            v2 = v2.astype(jnp.float32)
        cols.append(jax.lax.bitcast_convert_type(v2, jnp.int32))
    cols += [c.astype(jnp.int32)[:, None] for c in int_cols]
    packed = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    return packed, (dtype, d_vals, half, squeeze, len(int_cols))


def unpack_wire(recv: jax.Array, meta: tuple
                ) -> Tuple[Optional[jax.Array], List[jax.Array]]:
    """Exact inverse of :func:`pack_wire` (bitcast round-trip)."""
    dtype, d_vals, half, squeeze, n_int = meta
    n_v = recv.shape[1] - n_int
    ints_out = [recv[:, n_v + i] for i in range(n_int)]
    if dtype is None:
        return None, ints_out
    if half:
        v_out = jax.lax.bitcast_convert_type(recv[:, :n_v], dtype)
        v_out = v_out.reshape(v_out.shape[0], -1)[:, :d_vals]
    else:
        v_out = jax.lax.bitcast_convert_type(recv[:, :n_v],
                                             jnp.float32).astype(dtype)
    if squeeze:
        v_out = v_out[:, 0]
    return v_out, ints_out


def fused_all_to_all(vals: Optional[jax.Array], int_cols: Sequence[jax.Array],
                     axis) -> Tuple[Optional[jax.Array], List[jax.Array]]:
    """Deliver value columns + int32 metadata columns in ONE all_to_all.

    ``vals`` [N, D] (or [N], or None) float payload; ``int_cols`` are [N]
    int32 arrays (slot ids, expert ids, ...). The columns are packed into
    a single int32 wire array (:func:`pack_wire` — values bitcast,
    half-width payloads two per lane, never inflating the collective
    bytes), so each
    NoC round issues a single collective; the round-trip is exact.
    """
    packed, meta = pack_wire(vals, int_cols)
    recv = noc_all_to_all(packed, axis)
    return unpack_wire(recv, meta)


@functools.lru_cache(maxsize=None)
def wire_words(n_int_cols: int) -> int:
    """int32 columns of the wire :func:`pack_wire` makes from one float32
    value column and ``n_int_cols`` int32 columns (read off its output
    shape, so it follows the packing)."""
    packed = jax.eval_shape(
        lambda v, *c: pack_wire(v, c)[0],
        jax.ShapeDtypeStruct((1, 1), jnp.float32),
        *[jax.ShapeDtypeStruct((1,), jnp.int32)] * n_int_cols)
    assert packed.dtype == jnp.int32
    return int(packed.shape[1])


# ---------------------------------------------------------------------------
# owner-routed rounds (bucket + fused a2a), flat and hierarchical
# ---------------------------------------------------------------------------

def route_wire(n_shards: int, caps: Sequence[int],
               pods: Optional[Tuple[int, int]] = None,
               signal: bool = False) -> Tuple[Tuple[int, int], ...]:
    """``(rows, int32 words per row)`` of each all_to_all operand that one
    shard sends in one owner-routed round of a one-column float32 payload:
    :func:`owner_route` (one stage of ``n_shards`` buckets of ``caps[0]``
    slots, carrying the destination slot) or, with ``pods = (n_intra,
    n_pods)``, :func:`owner_route_hier` (``n_intra`` buckets of
    ``caps[0]`` carrying pod coordinate and slot, then ``n_pods`` of
    ``caps[1]`` carrying the slot). ``signal=True`` is the split-phase
    form (:func:`owner_route_start` / :func:`owner_route_hier_start`),
    whose collective carries one more row per destination bucket."""
    extra = 1 if signal else 0
    if pods is None:
        (cap,) = caps
        return ((n_shards * (cap + extra), wire_words(1)),)
    (n_intra, n_pods), (cap1, cap2) = pods, caps
    return ((n_intra * (cap1 + extra), wire_words(2)),
            (n_pods * (cap2 + extra), wire_words(1)))


def owner_route(vals, slot_ids, owner, valid, n_shards, cap, axis):
    """One flat NoC round: route ``(slot_ids, vals)`` tasks to ``owner``.

    Per-shard (call inside shard_map). vals [N] f32 payload, slot_ids [N]
    int32 destination slot at the owner, owner [N] in [0, n_shards).
    Returns (recv_slot [n_shards*cap], recv_val, n_drop_local) — recv_slot
    is -1 for empty queue entries; n_drop_local counts this shard's
    IQ-overflow drops (psum over ``axis`` for the global count).
    """
    xb, (slot_b,), _, n_drop = bucket(vals[:, None], owner, valid,
                                      [slot_ids], n_shards, cap)
    recv_vals, (recv_slot,) = fused_all_to_all(xb, [slot_b], axis)
    return recv_slot, recv_vals[:, 0], n_drop


def owner_route_hier(vals, slot_ids, owner, valid, n_intra, intra_axis,
                     n_pods, pod_axis, cap1, cap2):
    """Two-stage pod/portal NoC round (paper §III-A two-level torus).

    Stage 1 (tile-NoC): tasks go to the device in the *sender's* pod with
    the destination's intra-pod coordinate — the per-pod portal — so every
    package-boundary message is aggregated there. Stage 2 (die-NoC): the
    portal forwards over the pod axis, exactly one die crossing.
    Returns (recv_slot [n_pods*cap2], recv_val, n_drop_local).
    """
    e_coord = owner % n_intra
    p_coord = owner // n_intra
    xb, (pc_b, slot_b), _, drop1 = bucket(vals[:, None], e_coord, valid,
                                          [p_coord, slot_ids], n_intra, cap1)
    v1, (pc1, slot1) = fused_all_to_all(xb, [pc_b, slot_b], intra_axis)
    valid1 = pc1 >= 0
    xb2, (slot2_b,), _, drop2 = bucket(v1, jnp.maximum(pc1, 0), valid1,
                                       [slot1], n_pods, cap2)
    v2, (recv_slot,) = fused_all_to_all(xb2, [slot2_b], pod_axis)
    return recv_slot, v2[:, 0], drop1 + drop2


# ---------------------------------------------------------------------------
# split-phase rounds (the pipelined execution shape's communication edge)
# ---------------------------------------------------------------------------
#
# ``round_mode="pipelined"`` in :func:`repro.sparse.program.run_program`
# rotates the round loop: the collective for round k is LAUNCHED at the
# tail of loop iteration k-1 and its receive-reduce is consumed at the
# head of iteration k — the in-flight wire buffer is the loop carry (the
# double buffer). The helpers below split :func:`owner_route` /
# :func:`owner_route_hier` into that start/finish pair, and optionally
# ride a broadcast int32 *signal* (the while-loop's global frontier
# count) on the same collective as one extra row per destination bucket,
# so the pipelined loop needs NO per-round ``psum`` at all: one fused
# collective per round, where the lockstep shape issues four (a2a +
# message/drop/convergence psums).


def _a2a_with_signal(packed, n_blocks, signal, axis):
    """Tiled all_to_all of a packed wire array [n_blocks*rows, C] with one
    broadcast signal row appended per destination block.

    Every peer receives the sender's int32 ``signal`` (in column 0 of
    the extra row); the task rows' bytes are untouched — the
    exchanged blocks are simply [rows+1, C] instead of [rows, C], so the
    stripped receive buffer is value-identical to the plain collective.
    Returns ``(recv [n_blocks*rows, C], gsignal)`` where ``gsignal`` is
    the sum of all senders' signals — a global reduction ridden on the
    collective the round pays anyway (+1/rows wire overhead).
    """
    total, c = packed.shape
    rows = total // n_blocks
    sig_row = jnp.zeros((n_blocks, 1, c), packed.dtype).at[:, 0, 0].set(
        jnp.asarray(signal, packed.dtype))
    wire = jnp.concatenate([packed.reshape(n_blocks, rows, c), sig_row],
                           axis=1).reshape(n_blocks * (rows + 1), c)
    recv = noc_all_to_all(wire, axis).reshape(n_blocks, rows + 1, c)
    gsignal = jnp.sum(recv[:, rows, 0])
    return recv[:, :rows].reshape(n_blocks * rows, c), gsignal


def owner_route_start(vals, slot_ids, owner, valid, n_shards, cap, axis,
                      signal):
    """Produce half of one flat NoC round: bucket + pack + the fused
    collective (with ``signal`` ridden along, see :func:`_a2a_with_signal`).

    Returns ``(recv_wire, meta, n_drop_local, gsignal)``; hand
    ``(recv_wire, meta)`` to :func:`owner_route_finish` — possibly across
    a loop-carry boundary — for the exact :func:`owner_route` receive
    values. ``meta`` is static (shape/dtype bookkeeping), so only the
    wire buffer itself is carried.
    """
    xb, (slot_b,), _, n_drop = bucket(vals[:, None], owner, valid,
                                      [slot_ids], n_shards, cap)
    packed, meta = pack_wire(xb, [slot_b])
    recv, gsignal = _a2a_with_signal(packed, n_shards, signal, axis)
    return recv, meta, n_drop, gsignal


def owner_route_finish(recv_wire, meta):
    """Consume half: unpack the carried wire buffer into
    ``(recv_slot, recv_val)`` — feed :func:`reduce_received` to fold the
    receive-reduce into the communication edge."""
    recv_vals, (recv_slot,) = unpack_wire(recv_wire, meta)
    return recv_slot, recv_vals[:, 0]


def owner_route_hier_start(vals, slot_ids, owner, valid, n_intra,
                           intra_axis, n_pods, pod_axis, cap1, cap2,
                           signal):
    """Produce half of one pod/portal round (both stages complete here —
    stage-2 bucketing needs stage-1's receive, so the die-NoC edge is the
    one the pipelined loop carries). The signal crosses both stages:
    stage 1 sums it pod-locally at every portal, stage 2 sums the pod
    totals, so ``gsignal`` is the same global sum the flat path yields.
    Returns ``(recv_wire2, meta2, n_drop_local, gsignal)``."""
    e_coord = owner % n_intra
    p_coord = owner // n_intra
    xb, (pc_b, slot_b), _, drop1 = bucket(vals[:, None], e_coord, valid,
                                          [p_coord, slot_ids], n_intra, cap1)
    packed1, meta1 = pack_wire(xb, [pc_b, slot_b])
    recv1, sig1 = _a2a_with_signal(packed1, n_intra, signal, intra_axis)
    v1, (pc1, slot1) = unpack_wire(recv1, meta1)
    valid1 = pc1 >= 0
    xb2, (slot2_b,), _, drop2 = bucket(v1, jnp.maximum(pc1, 0), valid1,
                                       [slot1], n_pods, cap2)
    packed2, meta2 = pack_wire(xb2, [slot2_b])
    recv2, gsignal = _a2a_with_signal(packed2, n_pods, sig1, pod_axis)
    return recv2, meta2, drop1 + drop2, gsignal


def local_route(vals, slot_ids, dest, valid, n_buckets, cap):
    """Admission of one round whose producer and consumer are the same
    shard (a one-device flat launch): rank and capacity test, with no
    bucket array, no wire and no collective — the task stream itself is
    the receive buffer.

    The kept set is :func:`bucket`'s: the first ``cap`` valid tasks per
    channel in array order, ranked by the same rank, so the drop count
    is the same too. Returns ``(recv_slot, recv_val, n_drop)`` as
    :func:`owner_route` does, with ``recv_slot`` -1 for every task not
    kept, ready for :func:`reduce_received`.
    """
    pos = positions_by_dest(dest, valid, n_buckets)
    keep = valid & (pos < cap)
    return jnp.where(keep, slot_ids, -1), vals, jnp.sum(valid & ~keep)


def local_route_reduce(vals, slot_ids, dest, valid, n_buckets, cap, n_local,
                       op):
    """One whole round with a LOCAL communication edge:
    :func:`local_route` then :func:`reduce_received` straight off the
    task stream, never materializing the ``[n_buckets*cap]`` bucket array
    or re-reading it at the receiver.

    The result and drop count are bit-identical to ``bucket`` +
    :func:`reduce_received` for ``min`` / ``store`` with any number of
    buckets (order-insensitive, exact in f32), and for ``add`` with ONE
    bucket: there the kept tasks are summed in array order, which is the
    bucket's slot order. With several buckets the bucket array is ordered
    by destination, not by array index, so ``add`` raises
    ``ValueError``. Returns ``(y [n_local], n_drop)``.
    """
    if op == "add" and n_buckets > 1:
        raise ValueError(f"local_route_reduce sums in array order, which "
                         f"is bucket order only for one bucket, got "
                         f"{n_buckets}")
    recv_slot, recv_val, n_drop = local_route(vals, slot_ids, dest, valid,
                                              n_buckets, cap)
    return reduce_received(recv_slot, recv_val, n_local, op), n_drop


def reduce_received(recv_slot, recv_val, n_local, op):
    """Apply received tasks at the owner: segment add/min/store into local
    slots.

    ``op='store'`` is a last-writer overwrite with a *deterministic*
    tie-break: among duplicate destinations the maximum value wins —
    independent of bucket/slot arrival order, and by construction the same
    winner the analytic ``TaskEngine._reduce(op='store')`` picks for the
    same task stream (differential-tested in tests/test_core_engine.py).
    Slots that received no task read as 0.
    """
    valid = recv_slot >= 0
    seg = jnp.where(valid, recv_slot, n_local)
    if op == "add":
        y = jax.ops.segment_sum(jnp.where(valid, recv_val, 0.0), seg,
                                num_segments=n_local + 1)[:n_local]
    elif op == "min":
        y = jax.ops.segment_min(jnp.where(valid, recv_val, jnp.inf), seg,
                                num_segments=n_local + 1)[:n_local]
        y = jnp.where(jnp.isfinite(y), y, jnp.inf)
    elif op == "store":
        y = jax.ops.segment_max(jnp.where(valid, recv_val, -jnp.inf), seg,
                                num_segments=n_local + 1)[:n_local]
        y = jnp.where(jnp.isfinite(y), y, 0.0)
    else:
        raise ValueError(op)
    return y
