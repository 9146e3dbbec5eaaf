"""Pallas TPU kernels: analytics leaf-scan variants (count / collect /
polygon) over the hierarchically-pruned candidate tiles.

The boolean ``descent_scan`` kernel answers "any entry in the rect?".
The geosocial analytics classes (:mod:`repro.queries`) need richer leaf
scans over the *same* compacted candidate lists phase 1 produces:

* **count** (``count_scan_pallas``) — per-query exact hit count.  The
  boolean scan tolerates duplicate candidate tiles (idempotent OR); a
  sum does not, so padding slots are masked structurally: active
  candidates are strictly ascending and padding repeats the last active
  tile, hence a non-increasing step (``cand[i,k] <= cand[i,k-1]``) is
  padding and contributes zero.

* **collect** (``collect_scan_pallas``) — per-(query, candidate-lane)
  payload id or ``ID_SENTINEL``.  The scan writes the id plane masked
  by the exact hit test (and the same duplicate-tile mask), producing a
  ``(B, K*TP)`` matrix whose non-sentinel entries are exactly the hit
  ids; a fused XLA sort then yields the K smallest ids per query (the
  canonical collect order) with the sentinel sorting last.

* **polygon** (``polygon_scan_pallas``) — boolean RangeReach with a
  convex-polygon region: the query rect is the polygon's bbox and each
  query carries ``NE`` half-planes ``A*x + B*y <= C`` (float32, inert
  padding ``A=B=0, C=+inf``) evaluated against the entry point inside
  the leaf test — the postfilter pushed into the scan.  Entries must be
  points (2DReach's degenerate boxes); the float32 mul/add/compare
  sequence mirrors ``core.polygon.points_in_polygon_region`` op for op,
  which is what makes host and device bit-identical.

Every kernel has a dense jnp reference (``*_ref``) scanning the whole
arena — the exactness oracle for unit tests and a fused XLA fallback.
All run under ``interpret=True`` on CPU; on TPU the same calls compile
to real kernels (query tile on sublanes, as in :mod:`.kernel`).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel import TB, TP, box_hits, query_rows, query_specs

# payload-id sentinel for collect padding/misses: sorts after every real
# vertex id and survives the int32 round trip
ID_SENTINEL = np.int32(np.iinfo(np.int32).max)


def _hit_mask(e, q, qse, tile, *, dim: int, tp: int):
    """(TB, TP) exact per-entry test shared by the scan variants:
    arena-slice membership AND box intersection."""
    gidx = tile * tp + jax.lax.broadcasted_iota(jnp.int32, (1, tp), 1)
    ok = (gidx >= qse[:, 0:1]) & (gidx < qse[:, 1:2])
    return ok & box_hits(e, q, dim)


def _dup_slot(cand_ref, i, k):
    """True iff candidate slot k of query tile i is padding: actives are
    strictly ascending, padding repeats the last active tile."""
    prev = cand_ref[i, jnp.maximum(k - 1, 0)]
    return (k > 0) & (cand_ref[i, k] <= prev)


# --------------------------------------------------------------------------
# Count
# --------------------------------------------------------------------------

def _count_kernel(cand_ref, e_ref, q_ref, qse_ref, o_ref, *,
                  dim: int, tp: int):
    i, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    ok = _hit_mask(e_ref[...], q_ref[...], qse_ref[...], cand_ref[i, k],
                   dim=dim, tp=tp)
    cnt = jnp.sum(ok.astype(jnp.int32), axis=1, keepdims=True)
    o_ref[...] = o_ref[...] + jnp.where(_dup_slot(cand_ref, i, k), 0, cnt)


@functools.partial(jax.jit, static_argnames=("dim", "interpret", "tb", "tp"))
def count_scan_pallas(
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
    """(B,) int32 exact hit counts over the K candidate tiles.

    ``cand`` must be a ``compact_candidates`` list (actives strictly
    ascending, then the last active repeated) covering every tile with a
    possible hit — the prune phase guarantees the superset, the exact
    leaf test makes the count independent of superfluous tiles.
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
        functools.partial(_count_kernel, dim=dim, tp=tp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret,
    )(cand, entries_soa, *query_rows(rects_soa, qstart, qend))
    return out[:, 0]


def count_scan_ref(entries_soa, rects_soa, qstart, qend, *, dim: int = 2,
                   tp: int = TP):
    """Dense jnp oracle: exact counts scanning the whole arena."""
    P = entries_soa.shape[1]
    gidx = jnp.arange(P, dtype=jnp.int32)[None, :]
    ok = (gidx >= qstart[:, None]) & (gidx < qend[:, None])
    for a in range(dim):
        ok = ok & (entries_soa[a][None, :] <= rects_soa[dim + a][:, None])
        ok = ok & (entries_soa[dim + a][None, :] >= rects_soa[a][:, None])
    return jnp.sum(ok, axis=1).astype(jnp.int32)


# --------------------------------------------------------------------------
# Collect
# --------------------------------------------------------------------------

def _collect_kernel(cand_ref, e_ref, ids_ref, q_ref, qse_ref, o_ref,
                    *, dim: int, tp: int):
    i, k = pl.program_id(0), pl.program_id(1)
    ok = _hit_mask(e_ref[...], q_ref[...], qse_ref[...], cand_ref[i, k],
                   dim=dim, tp=tp)
    ok = ok & ~_dup_slot(cand_ref, i, k)
    ids = ids_ref[...]                       # (1, tp) payload ids
    o_ref[...] = jnp.where(ok, ids, ID_SENTINEL)


@functools.partial(jax.jit, static_argnames=("dim", "interpret", "tb", "tp"))
def collect_scan_pallas(
    cand: jax.Array,          # (B // tb, K) int32 candidate leaf tiles
    entries_soa: jax.Array,   # (2*dim, P) float32, P % tp == 0
    ids_soa: jax.Array,       # (1, P) int32 payload ids (sentinel padding)
    rects_soa: jax.Array,     # (2*dim, B) float32, B % tb == 0
    qstart: jax.Array,        # (B,) int32
    qend: jax.Array,          # (B,) int32
    *,
    dim: int = 2,
    interpret: bool = False,
    tb: int = TB,
    tp: int = TP,
) -> jax.Array:
    """(B, K*tp) int32 — the hit payload ids of each query (every other
    slot ``ID_SENTINEL``).  Sort rows and keep the prefix for the K
    smallest ids; count non-sentinels for the exact total."""
    two_dim, P = entries_soa.shape
    _, B = rects_soa.shape
    assert two_dim == 2 * dim
    assert P % tp == 0 and B % tb == 0, (P, B)
    assert ids_soa.shape == (1, P)
    nb = B // tb
    K = cand.shape[1]
    assert cand.shape == (nb, K)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, K),
        in_specs=[
            pl.BlockSpec((two_dim, tp), lambda i, k, cand: (0, cand[i, k])),
            pl.BlockSpec((1, tp), lambda i, k, cand: (0, cand[i, k])),
            *query_specs(tb, two_dim, lambda i, k, cand: i),
        ],
        out_specs=pl.BlockSpec((tb, tp), lambda i, k, cand: (i, k)),
    )
    return pl.pallas_call(
        functools.partial(_collect_kernel, dim=dim, tp=tp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K * tp), jnp.int32),
        interpret=interpret,
    )(cand, entries_soa, ids_soa, *query_rows(rects_soa, qstart, qend))


def collect_scan_ref(entries_soa, ids_soa, rects_soa, qstart, qend, *,
                     dim: int = 2, tp: int = TP):
    """Dense jnp oracle: (B, P) ids-or-sentinel over the whole arena."""
    P = entries_soa.shape[1]
    gidx = jnp.arange(P, dtype=jnp.int32)[None, :]
    ok = (gidx >= qstart[:, None]) & (gidx < qend[:, None])
    for a in range(dim):
        ok = ok & (entries_soa[a][None, :] <= rects_soa[dim + a][:, None])
        ok = ok & (entries_soa[dim + a][None, :] >= rects_soa[a][:, None])
    return jnp.where(ok, ids_soa[0][None, :], ID_SENTINEL)


# --------------------------------------------------------------------------
# Polygon (half-plane postfilter in the leaf scan)
# --------------------------------------------------------------------------

def _polygon_kernel(cand_ref, e_ref, l_ref, q_ref, qse_ref, o_ref, *,
                    dim: int, tp: int, ne: int):
    i, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    e = e_ref[...]
    ok = _hit_mask(e, q_ref[...], qse_ref[...], cand_ref[i, k], dim=dim,
                   tp=tp)
    # half-plane postfilter on the entry point (entries are degenerate
    # point boxes, so the min plane is the coordinate); same f32
    # mul/add/compare sequence as points_in_polygon_region
    x = e[0:1, :]
    y = e[1:2, :]
    lines = l_ref[...]                       # (TB, 3*ne)
    for hp in range(ne):
        A = lines[:, hp:hp + 1]
        Bc = lines[:, ne + hp:ne + hp + 1]
        C = lines[:, 2 * ne + hp:2 * ne + hp + 1]
        ok = ok & ((A * x + Bc * y) <= C)
    o_ref[...] = o_ref[...] | jnp.max(ok.astype(jnp.int32), axis=1,
                                      keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("dim", "interpret", "tb", "tp", "ne")
)
def polygon_scan_pallas(
    cand: jax.Array,          # (B // tb, K) int32 candidate leaf tiles
    entries_soa: jax.Array,   # (2*dim, P) float32, P % tp == 0
    rects_soa: jax.Array,     # (2*dim, B) float32 polygon bboxes
    lines_soa: jax.Array,     # (3*ne, B) float32 half-planes [A.., B.., C..]
    qstart: jax.Array,        # (B,) int32
    qend: jax.Array,          # (B,) int32
    *,
    ne: int,
    dim: int = 2,
    interpret: bool = False,
    tb: int = TB,
    tp: int = TP,
) -> jax.Array:
    """(B,) int32 0/1 — any entry point inside bbox AND all ``ne``
    half-planes (the convex-polygon region).  OR over candidate tiles is
    idempotent, so duplicate padding tiles need no masking."""
    two_dim, P = entries_soa.shape
    _, B = rects_soa.shape
    assert two_dim == 2 * dim == 4, "polygon regions are 2-D point queries"
    assert P % tp == 0 and B % tb == 0, (P, B)
    assert lines_soa.shape == (3 * ne, B)
    nb = B // tb
    K = cand.shape[1]
    assert cand.shape == (nb, K)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, K),
        in_specs=[
            pl.BlockSpec((two_dim, tp), lambda i, k, cand: (0, cand[i, k])),
            pl.BlockSpec((tb, 3 * ne), lambda i, k, cand: (i, 0)),
            *query_specs(tb, two_dim, lambda i, k, cand: i),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i, k, cand: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_polygon_kernel, dim=dim, tp=tp, ne=ne),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret,
    )(cand, entries_soa, lines_soa.T, *query_rows(rects_soa, qstart, qend))
    return out[:, 0]


def polygon_scan_ref(entries_soa, rects_soa, lines_soa, qstart, qend, *,
                     ne: int, dim: int = 2):
    """Dense jnp oracle for ``polygon_scan_pallas`` (same contract)."""
    P = entries_soa.shape[1]
    gidx = jnp.arange(P, dtype=jnp.int32)[None, :]
    ok = (gidx >= qstart[:, None]) & (gidx < qend[:, None])
    for a in range(dim):
        ok = ok & (entries_soa[a][None, :] <= rects_soa[dim + a][:, None])
        ok = ok & (entries_soa[dim + a][None, :] >= rects_soa[a][:, None])
    x = entries_soa[0][None, :]
    y = entries_soa[1][None, :]
    for hp in range(ne):
        A = lines_soa[hp][:, None]
        Bc = lines_soa[ne + hp][:, None]
        C = lines_soa[2 * ne + hp][:, None]
        ok = ok & ((A * x + Bc * y) <= C)
    return jnp.any(ok, axis=1).astype(jnp.int32)
