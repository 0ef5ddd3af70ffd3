"""Histogram Pallas TPU kernel — the paper's Histogram app, TPU-native.

Hardware adaptation (DESIGN.md §2): DCRA scatters (bin, +1) messages to the
bin's owner tile. A TPU has no scatter unit — the vector-unit rendering is
a compare + accumulate: elements stream lane-dense as [rows, 128] tiles,
each row of 128 elements is compared against a [bins, 128] block of bin
ids, and the hits accumulate into per-lane partial counts that the wrapper
sums across lanes. Bins are tiled over the grid's first axis so
arbitrarily many bins stream through VMEM; element tiles run over the
second (innermost) axis and accumulate into the output block. The
accumulation axis must be innermost: a TPU output block is written back
when its index changes and is not read back on a later visit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128      # elements per tile row
ROW_TILE = 64    # rows per grid step
BIN_TILE = 256   # bins per grid step


def _hist_kernel(elems_ref, out_ref, *, bin_tile):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = (pl.program_id(0) * bin_tile
            + jax.lax.broadcasted_iota(jnp.int32, (bin_tile, LANES), 0))

    def slab(k, acc):
        rows = elems_ref[pl.ds(pl.multiple_of(k * 8, 8), 8), :]   # [8, 128]
        for r in range(8):
            acc += (bins == rows[r:r + 1, :]).astype(jnp.int32)
        return acc

    out_ref[...] += jax.lax.fori_loop(
        0, elems_ref.shape[0] // 8, slab,
        jnp.zeros((bin_tile, LANES), jnp.int32))


def histogram_pallas(elements: jax.Array, n_bins: int,
                     interpret: bool = True) -> jax.Array:
    """elements: [N] int32 in [0, n_bins). Returns [n_bins] int32 counts.

    Any N / n_bins works: the element tail is padded with a -1 sentinel
    (matches no bin — negative ids are therefore also safe no-ops in the
    input itself, e.g. the task streams' padding entries) and the bin
    axis is padded to the bin tile and sliced off the result.
    """
    n = elements.shape[0]
    if n == 0:                       # zero-size grid is a pallas error
        return jnp.zeros((n_bins,), jnp.int32)
    rows = -(-n // LANES)
    tr = min(ROW_TILE, -(-rows // 8) * 8)
    rows_p = -(-rows // tr) * tr
    bt = min(BIN_TILE, -(-n_bins // 8) * 8)
    nb_pad = -(-n_bins // bt) * bt
    elems = jnp.pad(elements.astype(jnp.int32), (0, rows_p * LANES - n),
                    constant_values=-1).reshape(rows_p, LANES)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, bin_tile=bt),
        grid=(nb_pad // bt, rows_p // tr),
        in_specs=[pl.BlockSpec((tr, LANES), lambda j, i: (i, 0))],
        out_specs=pl.BlockSpec((bt, LANES), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((nb_pad, LANES), jnp.int32),
        interpret=interpret,
        name="histogram",
    )(elems)
    return jnp.sum(out, axis=1)[:n_bins]
