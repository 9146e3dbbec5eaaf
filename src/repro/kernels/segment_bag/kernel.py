"""Pallas TPU kernel: fused EmbeddingBag (gather + segment-sum).

JAX has no native EmbeddingBag; the recsys substrate (DIN's behaviour
sequences) and the GNN neighbour aggregation both reduce to

    out[s, :] = sum_{k : seg[k] == s} table[idx[k], :]

This kernel fuses the row gather with the segment accumulation so
gathered rows never round-trip through HBM: a tile of TL indices is
processed per grid step, each row loaded from the table with a dynamic
slice and accumulated into the output block (resident in VMEM across the
whole grid — the out index map is constant).  ``seg`` must be sorted
ascending (the host packs batches that way), padding rows carry
``seg == n_segments`` and land in a scratch row that is dropped.

On a real TPU the table block would be scalar-prefetched / DMA'd;
correctness here is validated in interpret mode, and the production
fallback (``jnp.take`` + ``segment_sum``) is ref.py — numerically
identical, used by the sharded training path where the table is
row-sharded over the model axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


TL = 8   # indices per grid step (unrolled)


def _segment_bag_kernel(idx_ref, seg_ref, w_ref, table_ref, o_ref, *, tl: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    for k in range(tl):
        idx = idx_ref[k]
        seg = seg_ref[k]
        row = table_ref[pl.ds(idx, 1), :]              # (1, D)
        w = w_ref[k].astype(row.dtype)
        o_ref[pl.ds(seg, 1), :] = o_ref[pl.ds(seg, 1), :] + w * row


@functools.partial(
    jax.jit, static_argnames=("n_segments", "interpret", "tl")
)
def segment_bag_pallas(
    table: jax.Array,     # (V, D) float32
    indices: jax.Array,   # (L,) int32, L % tl == 0 (padded with 0)
    segments: jax.Array,  # (L,) int32 sorted; padding -> n_segments
    weights: jax.Array,   # (L,) float32 per-lookup weight (panning: 0)
    *,
    n_segments: int,
    interpret: bool = False,
    tl: int = TL,
) -> jax.Array:
    """Returns (n_segments, D) segment-weighted sums of table rows."""
    V, D = table.shape
    L = indices.shape[0]
    assert L % tl == 0
    grid = (L // tl,)
    out = pl.pallas_call(
        functools.partial(_segment_bag_kernel, tl=tl),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tl,), lambda i: (i,)),
            pl.BlockSpec((tl,), lambda i: (i,)),
            pl.BlockSpec((tl,), lambda i: (i,)),
            pl.BlockSpec((V, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n_segments + 1, D), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_segments + 1, D), table.dtype),
        interpret=interpret,
    )(indices, segments, weights, table)
    return out[:n_segments]
