"""Pallas routing fast path — the NoC hot loop as kernels.

Every DCRA round funnels through :func:`repro.core.routing.bucket`: rank
each task within its destination bucket, admit the first ``cap`` per
channel, scatter the kept tasks into slot order, and (at the owner)
reduce the received stream into local state. The legacy ranking is a
``one_hot(dest, S)`` + cumsum — O(N*S) memory and FLOPs materialized in
HBM per stage, per round. This module provides the kernel tier of that
loop (the paper's IQ admission is *the* throughput limiter, §III/§VI):

* :func:`bucket_rank` — per-destination running counts live in VMEM and
  elements stream through in lane-dense [rows, 128] tiles: O(N + S*tiles)
  traffic instead of O(N*S). On TPU this is the Mosaic kernel
  (:func:`bucket_rank_pallas`, within-tile counts as MXU matmuls with
  triangular masks; its compile for v5e is guarded by
  tests/test_tpu_compile.py); off-TPU it lowers to the *same tiled
  algorithm* rendered in plain XLA (:func:`bucket_rank_xla` — within-tile
  ranks via an L*L compare, running counts via one scatter-add), never
  the Pallas interpreter, so the deployed fast path is interpreter-free
  on every backend. Tiny bucket counts keep the one-hot rank (it wins
  below :data:`ONEHOT_MAX_BUCKETS` — see the README routing section).
* :func:`bucket_scatter_pallas` — a fused admission kernel: one pass
  over the task stream producing ``(xb, ints, task_slot, n_drop)``
  (rank, capacity test, and slot scatter fused).
* :func:`reduce_received_pallas` — a fused receive-side add/min/store
  into local slots.

The two fused kernels store one scalar per element, which Mosaic refuses
("Cannot store scalars to VMEM"): they run in interpret mode only, and no
launch path calls them — every backend takes the rank + ``segment_sum``
scatter of :func:`repro.core.routing.bucket`.

Drop semantics are bit-identical to the one-hot path (first ``cap`` per
channel, array order), differential-tested in tests/test_route_kernels.py
— which is what keeps the analytic twins (``program_app_stats``,
``dse.shardcheck``) exact no matter which impl a launch resolves.

``impl`` knob (threaded from ``QueueConfig.route_impl`` / ``run_program``
/ ``dcra_scatter``): ``"pallas"`` (the fast path above), ``"sort"``
(argsort-by-dest + segment offsets — the same trick ``_pack_edges`` uses
host-side; pure XLA, selectable everywhere), ``"onehot"`` (legacy).
``None``/``"auto"`` resolve to the fast path, which autodetects the
backend exactly like :mod:`repro.kernels.ops` wrappers do (Mosaic on
TPU, native XLA elsewhere; ``interpret=True`` is for tests only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ELEM_TILE = 256          # fused kernels: elements streamed per grid step
LANES = 128              # rank kernel: elements per tile row
ROW_TILE = 256           # rank kernel: rows per grid step
SCAN_TILE = 32           # XLA tile-scan: within-tile rank compare width
ONEHOT_MAX_BUCKETS = 32  # below this S the one-hot rank wins off-TPU

ROUTE_IMPLS = ("pallas", "sort", "onehot")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def onehot_rank(dest, valid, n_buckets):
    """THE legacy one-hot-cumsum rank — the single copy both
    ``positions_by_dest(impl="onehot")`` and :func:`bucket_rank`'s
    narrow-bucket branch call, so the documented byte-for-byte
    equivalence between them cannot silently drift."""
    onehot = jax.nn.one_hot(dest, n_buckets, dtype=jnp.int32)
    onehot = onehot * valid[:, None].astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    return jnp.take_along_axis(pos, dest[:, None], axis=1)[:, 0]


def resolve_route_impl(impl=None) -> str:
    """``None``/``"auto"`` -> the fast path (``"pallas"``), which itself
    autodetects the backend (Mosaic on TPU, native XLA off-TPU)."""
    if impl in (None, "auto"):
        return "pallas"
    if impl not in ROUTE_IMPLS:
        raise ValueError(f"route_impl {impl!r} not in {ROUTE_IMPLS}")
    return impl


# ---------------------------------------------------------------------------
# bucket-rank: stable cumcount of each element within its destination
# ---------------------------------------------------------------------------

def _count_dot(a, b):
    """0/1 bf16 matmul with exact f32 counts. The precision is pinned: a
    caller's ``default_matmul_precision("highest")`` would otherwise ask
    Mosaic for an f32 contraction of bf16 operands, which it refuses."""
    return jnp.dot(a, b, precision=jax.lax.Precision.DEFAULT,
                   preferred_element_type=jnp.float32)


def _rank_kernel(key_ref, pos_ref, counts_ref, *, n_buckets):
    """One [TR, 128] element tile (row-major = array order): pos = running
    count + within-tile exclusive count, one bucket at a time.

    The within-tile count is two MXU matmuls on the 0/1 bucket mask — a
    strictly-upper [128, 128] mask counts earlier lanes of the same row,
    a strictly-lower [TR, TR] mask earlier rows — exact in f32 up to the
    tile size. Running counts are int32 (they exceed f32's exact range
    on large streams) and live in VMEM, one lane-broadcast row per
    bucket."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    key = key_ref[...]                                       # [TR, 128]
    tr = key.shape[0]
    lane_a = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    lane_b = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    earlier_lane = (lane_a < lane_b).astype(jnp.bfloat16)
    row_a = jax.lax.broadcasted_iota(jnp.int32, (tr, tr), 0)
    row_b = jax.lax.broadcasted_iota(jnp.int32, (tr, tr), 1)
    earlier_row = (row_b < row_a).astype(jnp.bfloat16)

    def one_bucket(s, pos):
        hit = key == s
        m = hit.astype(jnp.bfloat16)
        in_row = _count_dot(m, earlier_lane)
        above = jnp.sum(_count_dot(earlier_row, m), axis=1, keepdims=True)
        run = counts_ref[pl.ds(s, 1), :]                     # [1, 128]
        counts_ref[pl.ds(s, 1), :] = run + jnp.sum(hit.astype(jnp.int32))
        return jnp.where(hit, (in_row + above).astype(jnp.int32) + run, pos)

    pos_ref[...] = jax.lax.fori_loop(0, n_buckets, one_bucket,
                                     jnp.zeros(key.shape, jnp.int32))


def bucket_rank_pallas(dest: jax.Array, valid: jax.Array, n_buckets: int,
                       interpret: bool = True) -> jax.Array:
    """Stable position of each *valid* element within its destination
    bucket (invalid positions are 0 — callers mask with ``valid``).

    dest [N] int32 in [0, n_buckets); valid [N] bool. Invalid elements
    are folded into the key as -1 (no bucket), and the stream is laid out
    lane-dense as [rows, 128], tail-padded to the row tile, so any N
    works.
    """
    n = dest.shape[0]
    if n == 0:                       # zero-size grid is a pallas error
        return jnp.zeros((0,), jnp.int32)
    rows = -(-n // LANES)
    tr = min(ROW_TILE, -(-rows // 8) * 8)
    rows_p = -(-rows // tr) * tr
    key = jnp.where(valid, dest.astype(jnp.int32), -1)
    key = jnp.pad(key, (0, rows_p * LANES - n), constant_values=-1)
    pos = pl.pallas_call(
        functools.partial(_rank_kernel, n_buckets=n_buckets),
        grid=(rows_p // tr,),
        in_specs=[pl.BlockSpec((tr, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((n_buckets, LANES), jnp.int32)],
        interpret=interpret,
        name="bucket_rank",
    )(key.reshape(rows_p, LANES))
    return pos.reshape(-1)[:n]


def bucket_rank_xla(dest: jax.Array, valid: jax.Array, n_buckets: int,
                    tile: int = SCAN_TILE) -> jax.Array:
    """The tiled-scan rank in plain XLA — the interpreter-free off-TPU
    lowering of :func:`bucket_rank_pallas` (same algorithm: within-tile
    ranks + per-destination running counts across tiles).

    O(N*tile + tiles*S) instead of the one-hot's O(N*S): the within-tile
    rank is an L*L equality compare and the cross-tile running counts are
    one scatter-add + one short cumsum — nothing N*S ever materializes.
    """
    n = dest.shape[0]
    c = -(-n // tile)
    pad = c * tile - n
    # sentinel bucket S for invalid/padding: equal only to other invalid
    key = jnp.where(valid, dest.astype(jnp.int32), n_buckets)
    key = jnp.pad(key, (0, pad), constant_values=n_buckets)
    keyc = key.reshape(c, tile)
    eq = keyc[:, :, None] == keyc[:, None, :]                # [C, L, L]
    lower = jnp.tril(jnp.ones((tile, tile), bool), -1)
    within = jnp.sum((eq & lower).astype(jnp.int32), -1)     # [C, L]
    seg = (jnp.repeat(jnp.arange(c, dtype=jnp.int32), tile)
           * (n_buckets + 1) + key)
    cnt = jax.ops.segment_sum(jnp.ones(c * tile, jnp.int32), seg,
                              num_segments=c * (n_buckets + 1)
                              ).reshape(c, n_buckets + 1)
    run = (jnp.cumsum(cnt, axis=0) - cnt).reshape(-1)        # excl per tile
    return (within.reshape(-1) + run[seg])[:n]


def bucket_rank(dest: jax.Array, valid: jax.Array, n_buckets: int
                ) -> jax.Array:
    """The deployed fast-path rank: Mosaic on TPU, XLA tile-scan off-TPU
    (one-hot for tiny bucket counts, where it wins — see module doc)."""
    if _on_tpu():
        return bucket_rank_pallas(dest, valid, n_buckets, interpret=False)
    if n_buckets < ONEHOT_MAX_BUCKETS:
        # narrow bucket counts: the one-hot cumsum is cheap and beats the
        # scan's fixed costs — the shared legacy formulation, so these
        # shapes are byte-for-byte the baseline path
        return onehot_rank(dest, valid, n_buckets)
    return bucket_rank_xla(dest, valid, n_buckets)


# ---------------------------------------------------------------------------
# sort-impl bucketing: one argsort, then gathers — no segment-sum scatter
# ---------------------------------------------------------------------------

def bucket_sort_gather(x_tasks, dest, valid, aux_ints, n_buckets, cap):
    """The whole ``bucket()`` contract off ONE stable argsort, with ``xb``
    built by *gathering* from the sorted stream instead of scattering.

    The sort path used to rank via argsort and then hand the kept tasks
    to the generic ``segment_sum`` slot scatter — paying a second
    O(N)-segment reduction just to materialize the bucket array. But the
    argsort already placed bucket ``b``'s tasks contiguously: output slot
    ``(b, p)`` is simply the task at sorted position
    ``bucket_start[b] + p`` (when that run is long enough), so ``xb`` and
    every aux column are plain gathers of shape O(n_buckets*cap) — the
    ROADMAP follow-up from the PR 5 kernel tier. Drop semantics are
    bit-identical to the one-hot path (first ``cap`` per channel in array
    order — stable argsort preserves array order within a bucket),
    differential-tested in tests/test_route_kernels.py.

    Returns ``(xb [n_buckets*cap, D] (or [n_buckets*cap] for 1-D input),
    ints, task_slot, n_drop)`` exactly like
    :func:`repro.core.routing.bucket`.
    """
    n = dest.shape[0]
    total = n_buckets * cap
    squeeze = x_tasks.ndim == 1
    x2 = x_tasks[:, None] if squeeze else x_tasks
    if n == 0:
        xb = jnp.zeros((total, x2.shape[1]), x2.dtype)
        return (xb[:, 0] if squeeze else xb,
                [jnp.full((total,), -1, jnp.int32) for _ in aux_ints],
                jnp.zeros((0,), jnp.int32), jnp.int32(0))
    with jax.named_scope("dcra.route.rank"):
        # stable argsort by destination; invalid tasks sort to a sentinel
        key = jnp.where(valid, dest.astype(jnp.int32), n_buckets)
        order = jnp.argsort(key, stable=True)
        ks = key[order]
        run_start = jnp.searchsorted(ks, ks, side="left")
        pos_sorted = (jnp.arange(n, dtype=jnp.int32)
                      - run_start.astype(jnp.int32))
        pos = jnp.zeros(n, jnp.int32).at[order].set(pos_sorted)
    with jax.named_scope("dcra.route.scatter"):
        # bucket run offsets -> slot (b, p) gathers sorted index start[b] + p
        bins = jnp.arange(n_buckets, dtype=jnp.int32)
        b_start = jnp.searchsorted(ks, bins, side="left")
        b_end = jnp.searchsorted(ks, bins, side="right")
        slot_b = jnp.repeat(bins, cap)                       # [total]
        slot_p = jnp.tile(jnp.arange(cap, dtype=jnp.int32), n_buckets)
        src_sorted = b_start[slot_b] + slot_p
        filled = src_sorted < b_end[slot_b]
        src = order[jnp.minimum(src_sorted, n - 1)]
        xb = jnp.where(filled[:, None], x2[src], 0).astype(x2.dtype)
        ints = [jnp.where(filled, a.astype(jnp.int32)[src], -1)
                for a in aux_ints]
    keep = valid & (pos < cap)
    task_slot = jnp.where(keep, dest * cap + jnp.minimum(pos, cap - 1), -1)
    n_drop = jnp.sum(valid & ~keep)
    return (xb[:, 0] if squeeze else xb), ints, task_slot, n_drop


# ---------------------------------------------------------------------------
# fused bucket-scatter: rank + capacity test + slot scatter in one pass
# ---------------------------------------------------------------------------

def _scatter_kernel(dest_ref, valid_ref, x_ref, aux_ref, xb_ref, ints_ref,
                    slot_ref, counts_ref, *, n_buckets, cap, elem_tile):
    i = pl.program_id(0)
    total = n_buckets * cap

    @pl.when(i == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        xb_ref[...] = jnp.zeros_like(xb_ref)
        ints_ref[...] = jnp.full_like(ints_ref, -1)

    def body(e, _):
        d = jnp.clip(dest_ref[e], 0, n_buckets - 1)
        v = valid_ref[e] != 0
        c = counts_ref[0, d]
        keep = v & (c < cap)
        # kept tasks land in their slot; dropped/invalid ones hit the
        # garbage row `total`, sliced off by the wrapper
        w = jnp.where(keep, d * cap + jnp.minimum(c, cap - 1), total)
        xb_ref[w, :] = x_ref[e, :]
        ints_ref[w, :] = aux_ref[e, :]
        slot_ref[e] = jnp.where(keep, w, -1)
        counts_ref[0, d] = c + v.astype(jnp.int32)
        return 0

    jax.lax.fori_loop(0, elem_tile, body, 0)


def bucket_scatter_pallas(x, dest, valid, aux_ints, n_buckets, cap,
                          interpret: bool = True):
    """Fused capacity-bounded bucketing: ONE pass over the task stream.

    Same contract as :func:`repro.core.routing.bucket` — returns
    ``(xb [n_buckets*cap, D], ints (list of [n_buckets*cap] int32, -1 =
    empty), task_slot [N] (-1 = dropped), n_drop)`` with the identical
    first-``cap``-per-channel admission in array order.
    """
    n, d_cols = x.shape
    total = n_buckets * cap
    if n == 0:                       # zero-size grid is a pallas error
        return (jnp.zeros((total, d_cols), x.dtype),
                [jnp.full((total,), -1, jnp.int32) for _ in aux_ints],
                jnp.zeros((0,), jnp.int32), jnp.int32(0))
    k = max(1, len(aux_ints))
    aux = (jnp.stack([a.astype(jnp.int32) for a in aux_ints], axis=1)
           if aux_ints else jnp.zeros((n, 1), jnp.int32))
    et = min(ELEM_TILE, max(8, n))
    n_pad = -(-n // et) * et
    pad = n_pad - n
    dest_p = jnp.pad(dest.astype(jnp.int32), (0, pad))
    valid_p = jnp.pad(valid.astype(jnp.int32), (0, pad))
    x_p = jnp.pad(x, ((0, pad), (0, 0)))
    aux_p = jnp.pad(aux, ((0, pad), (0, 0)))
    xb, ints, slot = pl.pallas_call(
        functools.partial(_scatter_kernel, n_buckets=n_buckets, cap=cap,
                          elem_tile=et),
        grid=(n_pad // et,),
        in_specs=[pl.BlockSpec((et,), lambda i: (i,)),
                  pl.BlockSpec((et,), lambda i: (i,)),
                  pl.BlockSpec((et, d_cols), lambda i: (i, 0)),
                  pl.BlockSpec((et, k), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((total + 1, d_cols), lambda i: (0, 0)),
                   pl.BlockSpec((total + 1, k), lambda i: (0, 0)),
                   pl.BlockSpec((et,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((total + 1, d_cols), x.dtype),
                   jax.ShapeDtypeStruct((total + 1, k), jnp.int32),
                   jax.ShapeDtypeStruct((n_pad,), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, n_buckets), jnp.int32)],
        interpret=interpret,
    )(dest_p, valid_p, x_p, aux_p)
    task_slot = slot[:n]
    n_drop = jnp.sum(valid) - jnp.sum(task_slot >= 0)
    ints_out = [ints[:total, j] for j in range(len(aux_ints))]
    return xb[:total], ints_out, task_slot, n_drop


# ---------------------------------------------------------------------------
# fused receive-reduce: apply the received stream at the owner
# ---------------------------------------------------------------------------

_REDUCE_INIT = {"add": 0.0, "min": float("inf"), "store": float("-inf")}


def _reduce_kernel(slot_ref, val_ref, y_ref, *, n_local, op, elem_tile):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.full_like(y_ref, _REDUCE_INIT[op])

    def body(e, _):
        s = slot_ref[e]
        w = jnp.clip(jnp.where(s >= 0, s, n_local), 0, n_local)
        v = val_ref[e]
        if op == "add":
            y_ref[w] += jnp.where(s >= 0, v, 0.0)
        elif op == "min":
            y_ref[w] = jnp.minimum(y_ref[w], jnp.where(s >= 0, v, jnp.inf))
        else:                                                # "store" (max)
            y_ref[w] = jnp.maximum(y_ref[w], jnp.where(s >= 0, v, -jnp.inf))
        return 0

    jax.lax.fori_loop(0, elem_tile, body, 0)


def reduce_received_pallas(recv_slot, recv_val, n_local, op,
                           interpret: bool = True):
    """Fused owner-side reduce — same contract as
    :func:`repro.core.routing.reduce_received` (add/min/store; ``store``
    keeps the deterministic max-value tie-break)."""
    if op not in _REDUCE_INIT:
        raise ValueError(op)
    n = recv_slot.shape[0]
    if n == 0:                       # zero-size grid is a pallas error
        return jnp.full((n_local,), jnp.inf if op == "min" else 0.0,
                        jnp.float32)
    et = min(ELEM_TILE, max(8, n))
    n_pad = -(-n // et) * et
    pad = n_pad - n
    slot_p = jnp.pad(recv_slot.astype(jnp.int32), (0, pad),
                     constant_values=-1)
    val_p = jnp.pad(recv_val.astype(jnp.float32), (0, pad))
    y = pl.pallas_call(
        functools.partial(_reduce_kernel, n_local=n_local, op=op,
                          elem_tile=et),
        grid=(n_pad // et,),
        in_specs=[pl.BlockSpec((et,), lambda i: (i,)),
                  pl.BlockSpec((et,), lambda i: (i,))],
        out_specs=pl.BlockSpec((n_local + 1,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((n_local + 1,), jnp.float32),
        interpret=interpret,
    )(slot_p, val_p)[:n_local]
    if op == "min":
        return jnp.where(jnp.isfinite(y), y, jnp.inf)
    if op == "store":
        return jnp.where(jnp.isfinite(y), y, 0.0)
    return y
