"""Pallas TPU kernels: hierarchically-pruned RangeReach descent.

The legacy ``range_query`` kernel scans *every* leaf tile of the entry
arena for every query tile — correct, but pointer-chasing-era wasteful
once the forest grows.  This module is the device equivalent of an
R-tree descent, split into two phases so each phase is a dense,
tile-shaped kernel:

* **Phase 1 — prune** (``prune_tiles_pallas``): the entry arena is
  covered by a *tile pyramid*: one MBR per ``TP``-entry leaf tile
  (``fine``) and one MBR per ``COARSE_GROUP`` leaf tiles (``coarse``) —
  exactly the internal levels of an R-tree with fanout ``TP`` re-based
  onto the global arena so tiles align with the scan kernel's blocks.
  The kernel ANDs each query rect against the coarse level first (a
  ``pl.when`` gate skips the fine-level test for grid steps whose
  coarse MBRs miss every query of the block), then against the fine
  level and the query's ``[qstart, qend)`` arena slice.  Output: a
  per-(query-tile, leaf-tile) activity mask.

* **Phase 2 — masked scan** (``descent_scan_pallas``): a scalar-prefetch
  grid ``(B/TB, K)`` walks a *compacted candidate list* of leaf tiles
  per query tile (active tiles first, then the last active tile
  repeated — consecutive identical block indices elide the DMA), so
  only ``K`` tiles are fetched per query tile instead of all ``P/TP``.
  Scanning a superfluous tile is harmless: the leaf test re-masks by
  arena slice and exact box intersection, and the OR-accumulate is
  idempotent — exactness never depends on the mask.

Both kernels run under ``interpret=True`` on CPU; on TPU the same calls
compile to real kernels.  Every block obeys the TPU's (8, 128) rule:
the query tile sits on sublanes (``kernel.query_rows``), the coarse
plane enters repeated to fine-tile width so its block is lane-aligned
with the fine block, and the prune mask is written as
``(B // tb, 1, NTp)`` so each step's ``(1, tpt)`` row spans a full
middle dimension.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel import TB, TP, box_hits, query_rows, query_specs

TPT = 128        # fine-tile lanes per prune-kernel block
COARSE_GROUP = 8  # leaf tiles per coarse pyramid node


# --------------------------------------------------------------------------
# Tile pyramid (host, once per index upload)
# --------------------------------------------------------------------------

def build_tile_pyramid(
    entries_soa: np.ndarray, dim: int
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Aggregate SoA leaf entries into (fine, coarse) MBR planes.

    ``entries_soa`` is the (2*dim, Pp) plane layout of ``forest_to_soa``
    with Pp a multiple of TP; padding entries are impossible boxes
    (min > max) and only ever make tile MBRs *more* permissive along the
    axes they touch, so pruning stays conservative and phase-2 masking
    keeps it exact.

    Returns (fine_soa (2*dim, NTp), coarse_soa (2*dim, NCp), n_tiles)
    where n_tiles = Pp // TP is the true fine tile count, NTp rounds it
    up to TPT lanes and NCp rounds the coarse count up to
    TPT // COARSE_GROUP.
    """
    two_dim, Pp = entries_soa.shape
    assert two_dim == 2 * dim and Pp % TP == 0
    nt = Pp // TP
    tiled = entries_soa.reshape(two_dim, nt, TP)
    fine = np.empty((two_dim, nt), dtype=np.float32)
    fine[:dim] = tiled[:dim].min(axis=2)
    fine[dim:] = tiled[dim:].max(axis=2)

    nc = -(-nt // COARSE_GROUP)
    pad_f = nc * COARSE_GROUP
    fpad = np.empty((two_dim, pad_f), dtype=np.float32)
    fpad[:dim] = np.inf
    fpad[dim:] = -np.inf
    fpad[:, :nt] = fine
    grouped = fpad.reshape(two_dim, nc, COARSE_GROUP)
    coarse = np.empty((two_dim, nc), dtype=np.float32)
    coarse[:dim] = grouped[:dim].min(axis=2)
    coarse[dim:] = grouped[dim:].max(axis=2)

    ntp = max(TPT, -(-nt // TPT) * TPT)
    ncp = ntp // COARSE_GROUP
    # padding tiles can never intersect: min=+inf / max=-inf (extent-proof,
    # unlike a finite sentinel)
    fine_soa = np.empty((two_dim, ntp), dtype=np.float32)
    fine_soa[:dim] = np.inf
    fine_soa[dim:] = -np.inf
    fine_soa[:, :nt] = fine
    coarse_soa = np.empty((two_dim, ncp), dtype=np.float32)
    coarse_soa[:dim] = np.inf
    coarse_soa[dim:] = -np.inf
    coarse_soa[:, :nc] = coarse
    return fine_soa, coarse_soa, nt


# --------------------------------------------------------------------------
# Phase 1: hierarchical prune
# --------------------------------------------------------------------------

def _prune_kernel(f_ref, c_ref, q_ref, qse_ref, o_ref, *, dim: int,
                  tpt: int, tp: int):
    j = pl.program_id(1)
    q = q_ref[...]                       # (TB, 2*dim)

    # -- coarse level: internal MBRs gate the whole block ------------------
    cok = box_hits(c_ref[...], q, dim)   # (TB, tpt): each tile's group MBR
    any_c = jnp.max(cok.astype(jnp.int32)) > 0

    @pl.when(any_c)
    def _descend():
        gidx = j * tpt + jax.lax.broadcasted_iota(jnp.int32, (1, tpt), 1)
        # arena-slice overlap: fine tile g covers entries [g*tp, g*tp+tp)
        ok = ((gidx * tp < qse_ref[:, 1:2])
              & (gidx * tp + tp > qse_ref[:, 0:1]))       # (TB, tpt)
        ok = ok & box_hits(f_ref[...], q, dim) & cok
        o_ref[...] = jnp.max(ok.astype(jnp.int32), axis=0, keepdims=True)

    @pl.when(~any_c)
    def _pruned():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("dim", "interpret", "tb", "tpt", "tp", "group")
)
def prune_tiles_pallas(
    fine_soa: jax.Array,     # (2*dim, NTp) float32, NTp % tpt == 0
    coarse_soa: jax.Array,   # (2*dim, NTp // group) float32
    rects_soa: jax.Array,    # (2*dim, B) float32, B % tb == 0
    qstart: jax.Array,       # (B,) int32
    qend: jax.Array,         # (B,) int32
    *,
    dim: int = 2,
    interpret: bool = False,
    tb: int = TB,
    tpt: int = TPT,
    tp: int = TP,
    group: int = COARSE_GROUP,
) -> jax.Array:
    """(B // tb, NTp) int32 — 1 iff any query of tile i needs leaf tile j."""
    two_dim, ntp = fine_soa.shape
    _, B = rects_soa.shape
    assert two_dim == 2 * dim
    assert ntp % tpt == 0 and B % tb == 0, (ntp, B)
    assert coarse_soa.shape == (two_dim, ntp // group)
    nb = B // tb
    grid = (nb, ntp // tpt)
    mask = pl.pallas_call(
        functools.partial(_prune_kernel, dim=dim, tpt=tpt, tp=tp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((two_dim, tpt), lambda i, j: (0, j)),
            pl.BlockSpec((two_dim, tpt), lambda i, j: (0, j)),
            *query_specs(tb, two_dim, lambda i, j: i),
        ],
        out_specs=pl.BlockSpec((None, 1, tpt), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, ntp), jnp.int32),
        interpret=interpret,
    )(fine_soa, jnp.repeat(coarse_soa, group, axis=1),
      *query_rows(rects_soa, qstart, qend))
    return mask.reshape(nb, ntp)


def prune_tiles_ref(fine_soa, coarse_soa, rects_soa, qstart, qend, *,
                    dim: int = 2, tb: int = TB, tp: int = TP,
                    group: int = COARSE_GROUP):
    """Dense jnp oracle for ``prune_tiles_pallas`` (same contract)."""
    ntp = fine_soa.shape[1]
    B = rects_soa.shape[1]
    gidx = jnp.arange(ntp, dtype=jnp.int32)[None, :]
    ok = (gidx * tp < qend[:, None]) & (gidx * tp + tp > qstart[:, None])
    for a in range(dim):
        ok = ok & (fine_soa[a][None, :] <= rects_soa[dim + a][:, None])
        ok = ok & (fine_soa[dim + a][None, :] >= rects_soa[a][:, None])
    cok = jnp.ones((B, ntp // group), dtype=bool)
    for a in range(dim):
        cok = cok & (coarse_soa[a][None, :] <= rects_soa[dim + a][:, None])
        cok = cok & (coarse_soa[dim + a][None, :] >= rects_soa[a][:, None])
    ok = ok & jnp.repeat(cok, group, axis=1)
    return (
        jnp.any(ok.reshape(B // tb, tb, ntp), axis=1).astype(jnp.int32)
    )


# --------------------------------------------------------------------------
# Phase 2: masked leaf scan over compacted candidate tiles
# --------------------------------------------------------------------------

def _scan_kernel(cand_ref, e_ref, q_ref, qse_ref, o_ref, *, dim: int,
                 tp: int):
    i, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    tile = cand_ref[i, k]
    gidx = tile * tp + jax.lax.broadcasted_iota(jnp.int32, (1, tp), 1)
    ok = (gidx >= qse_ref[:, 0:1]) & (gidx < qse_ref[:, 1:2])  # (TB, TP)
    ok = ok & box_hits(e_ref[...], q_ref[...], dim)
    o_ref[...] = o_ref[...] | jnp.max(ok.astype(jnp.int32), axis=1,
                                      keepdims=True)


@functools.partial(jax.jit, static_argnames=("dim", "interpret", "tb", "tp"))
def descent_scan_pallas(
    cand: jax.Array,          # (B // tb, K) int32 candidate leaf tiles
    entries_soa: jax.Array,   # (2*dim, P) float32, P % tp == 0
    rects_soa: jax.Array,     # (2*dim, B) float32, B % tb == 0
    qstart: jax.Array,        # (B,) int32
    qend: jax.Array,          # (B,) int32
    *,
    dim: int = 2,
    interpret: bool = False,
    tb: int = TB,
    tp: int = TP,
) -> jax.Array:
    """(B,) int32 0/1 — OR over the K candidate tiles of each query tile.

    ``cand`` values must lie in [0, P // tp); duplicates are harmless
    (idempotent OR) and padding by repeating the last active tile keeps
    consecutive identical block indices, which the pipeline fetches only
    once.
    """
    two_dim, P = entries_soa.shape
    _, B = rects_soa.shape
    assert two_dim == 2 * dim
    assert P % tp == 0 and B % tb == 0, (P, B)
    nb = B // tb
    K = cand.shape[1]
    assert cand.shape == (nb, K)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, K),
        in_specs=[
            pl.BlockSpec((two_dim, tp), lambda i, k, cand: (0, cand[i, k])),
            *query_specs(tb, two_dim, lambda i, k, cand: i),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i, k, cand: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_scan_kernel, dim=dim, tp=tp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret,
    )(cand, entries_soa, *query_rows(rects_soa, qstart, qend))
    return out[:, 0]
