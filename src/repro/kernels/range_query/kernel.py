"""Pallas TPU kernel: batched AABB range probe over packed R-tree leaves.

The RangeReach hot path after 2DReach reduces a query to "does any leaf
entry of tree t intersect rect R".  On TPU the winning layout is not a
pointer descent but a **tiled scan with an OR-reduce**: queries are the
sublane axis (TB=8), leaf entries the lane axis (TP=128), and each grid
step tests a (TB x TP) tile of (query, entry) pairs on the VPU.  Each
query carries its tree's ``[start, end)`` slice of the global entry
arena; tiles outside the slice are masked.  The output is revisited
across the entry-tile grid dimension (constant index map) so the OR
accumulates in VMEM without touching HBM per tile.

Layout notes: entries are passed as structure-of-arrays ``(2*dim, P)``
— coordinate planes on the sublane axis, P on the lane axis — so a
single tile holds 128 entries x all coordinates.  Inside the kernel the
query tile sits on sublanes: rects arrive as ``(B, 2*dim)`` rows and the
arena slices as ``(B, 2)`` [qstart, qend] rows, so each block is
``(TB, full width)`` — legal under the TPU's (8, 128) block rule — and
a rect coordinate is a ``(TB, 1)`` column that broadcasts against an
entry plane's ``(1, TP)`` row with no relayout.  The public wrappers
keep the ``(2*dim, B)`` SoA / ``(B,)`` signatures and transpose in the
jitted wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


TB = 8     # query tile (sublanes)
TP = 128   # entry tile (lanes)


def box_hits(planes, rects, dim: int):
    """(TB, N) bool — entry/tile box ``planes`` (2*dim, N) [mins...,
    maxs...] intersect the query-tile ``rects`` (TB, 2*dim) rows:
    ``min <= rect_max`` and ``max >= rect_min`` on every axis."""
    ok = None
    for a in range(dim):
        t = ((planes[a:a + 1, :] <= rects[:, dim + a:dim + a + 1])
             & (planes[dim + a:dim + a + 1, :] >= rects[:, a:a + 1]))
        ok = t if ok is None else ok & t
    return ok


def query_rows(rects_soa, qstart, qend):
    """SoA rects (2*dim, B) + slices (B,) -> the kernels' sublane-major
    ``(B, 2*dim)`` rect rows and ``(B, 2)`` [qstart, qend] rows."""
    return rects_soa.T, jnp.stack([qstart, qend], axis=1).astype(jnp.int32)


def query_specs(tb: int, two_dim: int, index_map):
    """BlockSpecs for ``query_rows``' outputs: one (tb, width) block per
    query tile ``index_map(*grid) -> i``."""
    return [pl.BlockSpec((tb, two_dim), lambda *g: (index_map(*g), 0)),
            pl.BlockSpec((tb, 2), lambda *g: (index_map(*g), 0))]


def _range_query_kernel(e_ref, q_ref, qse_ref, o_ref, *, dim: int,
                        tp: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    e = e_ref[...]                     # (2*dim, TP)  [mins..., maxs...]
    gidx = j * tp + jax.lax.broadcasted_iota(jnp.int32, (1, tp), 1)
    valid = (gidx >= qse_ref[:, 0:1]) & (gidx < qse_ref[:, 1:2])
    ok = valid & box_hits(e, q_ref[...], dim)          # (TB, TP)
    hit = jnp.max(ok.astype(jnp.int32), axis=1, keepdims=True)
    o_ref[...] = o_ref[...] | hit


@functools.partial(
    jax.jit, static_argnames=("dim", "interpret", "tb", "tp")
)
def range_query_pallas(
    entries_soa: jax.Array,   # (2*dim, P) float32, P % tp == 0
    rects_soa: jax.Array,     # (2*dim, B) float32, B % tb == 0
    qstart: jax.Array,        # (B,) int32 — entry-arena slice per query
    qend: jax.Array,          # (B,) int32
    *,
    dim: int = 2,
    interpret: bool = False,
    tb: int = TB,
    tp: int = TP,
) -> jax.Array:
    """Returns (B,) int32 (0/1) — any entry in [qstart, qend) intersecting."""
    two_dim, P = entries_soa.shape
    _, B = rects_soa.shape
    assert two_dim == 2 * dim
    assert P % tp == 0 and B % tb == 0, (P, B)
    grid = (B // tb, P // tp)
    out = pl.pallas_call(
        functools.partial(_range_query_kernel, dim=dim, tp=tp),
        grid=grid,
        in_specs=[pl.BlockSpec((two_dim, tp), lambda i, j: (0, j)),
                  *query_specs(tb, two_dim, lambda i, j: i)],
        out_specs=pl.BlockSpec((tb, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret,
    )(entries_soa, *query_rows(rects_soa, qstart, qend))
    return out[:, 0]
