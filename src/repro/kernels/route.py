"""Pallas routing fast path — the NoC hot loop's rank as a kernel.

Every DCRA round funnels through :func:`repro.core.routing.bucket`: rank
each task within its destination bucket, admit the first ``cap`` per
channel, scatter the kept tasks into slot order, and (at the owner)
reduce the received stream into local state. The paper's IQ admission is
*the* throughput limiter (§III/§VI); its rank is :func:`bucket_rank`,
the one engine every launch runs. It picks its lowering from what it
observes:

* on TPU, the Mosaic kernel :func:`bucket_rank_pallas`: per-destination
  running counts live in VMEM and elements stream through in lane-dense
  [rows, 128] tiles, O(N + S*tiles) traffic instead of the one-hot's
  O(N*S), with within-tile counts as MXU matmuls on triangular masks
  (its compile for v5e is guarded by tests/test_tpu_compile.py);
* off TPU, the same tiled algorithm rendered in plain XLA
  (:func:`bucket_rank_xla`: within-tile ranks via an L*L compare,
  running counts via one scatter-add), never the Pallas interpreter;
* off TPU below :data:`ONEHOT_MAX_BUCKETS` buckets, the one-hot cumsum
  :func:`onehot_rank`, which is cheap there and beats the scan's fixed
  costs (see the README routing section).

Admission is first ``cap`` per channel in array order whichever lowering
runs, differential-tested against :func:`onehot_rank` in
tests/test_route_kernels.py — which is what keeps the analytic twins
(``program_app_stats``, ``dse.shardcheck``) exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128              # rank kernel: elements per tile row
ROW_TILE = 256           # rank kernel: rows per grid step
SCAN_TILE = 32           # XLA tile-scan: within-tile rank compare width
ONEHOT_MAX_BUCKETS = 32  # below this S the one-hot rank wins off-TPU


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def onehot_rank(dest, valid, n_buckets):
    """The one-hot-cumsum rank, O(N*S): :func:`bucket_rank`'s off-TPU
    branch below :data:`ONEHOT_MAX_BUCKETS` buckets, and the oracle the
    tests hold the tiled lowerings to."""
    onehot = jax.nn.one_hot(dest, n_buckets, dtype=jnp.int32)
    onehot = onehot * valid[:, None].astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    return jnp.take_along_axis(pos, dest[:, None], axis=1)[:, 0]


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
        # scan's fixed costs
        return onehot_rank(dest, valid, n_buckets)
    return bucket_rank_xla(dest, valid, n_buckets)
