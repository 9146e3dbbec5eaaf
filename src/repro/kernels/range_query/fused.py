"""Fused serving megakernel: single-launch route→prune→scan.

The two-phase engine (:mod:`.descent`) answers a batch with *three*
dispatches — a prune ``pallas_call``, a host round trip that buckets the
candidate capacity, and a scan ``pallas_call`` — plus a host-side pad.
The paper's point is that a 2DReach query is **one** R-tree lookup; this
module makes the device path agree:

* **Quantized MBR planes** (:class:`QuantGrid`): rects and tile MBRs are
  snapped onto an integer grid over the arena's extent — ``int16`` for
  the fine (leaf-tile) plane, ``int32`` for the coarse plane — with
  every bound rounded *outward* (mins down, maxs up, ±1 grid cell of
  slack so float32 scaling error can never round inward).  The
  quantized intersection test is therefore a provable superset of the
  float32 truth: pruning stays sound, the final leaf predicate stays
  exact f32.  (The int16 plane halves HBM bytes; in VMEM its 4 rows
  pad to a 16-row tile, the same footprint as 8 int32 rows.)
  Padding (±inf) bounds map to reserved sentinel codes that fail both
  halves of the intersect test, so padding tiles can never activate.

* **The megakernel** (:func:`fused_serve_pallas`): ONE ``pallas_call``
  over grid ``(B // TB,)``.  Each step holds its query tile's rects
  (quantized + exact), the whole quantized pyramid as full-array VMEM
  blocks, and the entry arena left in HBM/ANY.  The pyramid is not
  small: its 4 coordinate rows pad to a full (16, 128) int16 / (8, 128)
  int32 tile, so the fine plane and the coarse plane (repeated to fine
  width) take ~0.7 MB each per buffer at Gowalla x50 (22,272 tile
  lanes), ~0.25 MB each at a million venues.  In-kernel it (1)
  evaluates the hierarchical coarse∧fine prune, (2) counts the
  surviving leaf tiles, and (3) walks them in ascending order — slot
  k+1's tile is the smallest active lane above slot k's (a masked lane
  min, no host compaction, no materialized candidate matrix) — with
  double-buffered DMA: the next tile's HBM→VMEM copy is in flight while
  the current tile's exact f32 predicate evaluates.  A ``mode`` flag
  selects the epilogue — boolean OR, exact count, or collect
  (ids-or-sentinel written per worklist slot) — so one kernel serves
  ``query/count/collect_batch``.

* **The fused XLA path** (:func:`fused_serve_xla`): the same
  route→prune→compact→scan semantics as one fused XLA program (dense
  quantized prune, ascending compaction, gathered leaf tiles).  It is
  bit-identical to the megakernel and serves two roles: the oracle the
  kernel is tested against, and the serving implementation on backends
  where Pallas only interprets (CPU), where one compiled XLA program
  beats an emulated kernel.

Capacity contract: both paths scan at most ``kcap`` candidate tiles per
query tile and report the *true* per-tile candidate counts.  When any
count exceeds ``kcap`` the results are a partial scan — callers
(the engine's ratcheting high-water mark) must re-run at the next
power-of-two bucket.  Steady state never ratchets, so the fused trace
is compile-once like the two-phase path it replaces.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .analytics import ID_SENTINEL
from .descent import COARSE_GROUP
from .kernel import TB, TP, box_hits, query_rows, query_specs

# int16 fine-plane code space: finite bounds clip to [I16_LO, I16_HI];
# the values just outside are reserved for ±inf padding so an inert
# tile/rect fails both halves of the intersect test by construction.
I16_LO, I16_HI = -32767, 32766
I16_PAD_MIN, I16_PAD_MAX = 32767, -32768          # min=+inf / max=-inf
# int32 coarse-plane code space (2^20-cell grid, clip well inside int32)
I32_LO, I32_HI = -2_000_000, 2_000_000
I32_PAD_MIN, I32_PAD_MAX = 2_100_000, -2_100_000
_GRID16 = 60000.0       # fine grid cells across the arena extent
_GRID32 = float(2 ** 20)  # coarse grid cells


# --------------------------------------------------------------------------
# Quantization (outward-rounded, provably conservative)
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantGrid:
    """Per-axis affine maps onto the int16 / int32 code grids.

    ``code = (x - mid) * scale`` with mins floored (−1 slack) and maxs
    ceiled (+1 slack) before clipping into the finite code range: the
    slack cell absorbs the float32 scaling error (≤ ~0.01 cells for the
    int16 grid, ≤ ~0.1 for the int32 grid), so a quantized bound is
    always at least as permissive as the exact one.  Clipping is
    monotone, hence also conservative: out-of-extent values saturate
    toward "intersects more", never less.
    """

    mid: jax.Array   # (dim,) float32 extent midpoint
    s16: jax.Array   # (dim,) float32 cells-per-unit, fine grid
    s32: jax.Array   # (dim,) float32 cells-per-unit, coarse grid


def make_quant_grid(extent, dim: int) -> QuantGrid:
    """Grid from a ``(2*dim,)`` [mins..., maxs...] extent (``None`` /
    empty arena → a degenerate grid under which every finite bound maps
    near 0 — maximally permissive, still exact downstream)."""
    if extent is None:
        lo = np.zeros(dim, np.float64)
        hi = np.zeros(dim, np.float64)
    else:
        extent = np.asarray(extent, np.float64)
        lo, hi = extent[:dim], extent[dim:2 * dim]
    width = np.maximum(hi - lo, 1e-9)
    return QuantGrid(
        mid=jnp.asarray((lo + hi) / 2.0, jnp.float32),
        s16=jnp.asarray(_GRID16 / width, jnp.float32),
        s32=jnp.asarray(_GRID32 / width, jnp.float32),
    )


def _q_bounds(x, mid, s, *, lo_code, hi_code, pad_min, pad_max,
              is_min: bool, dtype):
    """Outward-rounded quantization of one bound plane (see QuantGrid)."""
    v = (x - mid) * s
    if is_min:
        q = jnp.clip(jnp.floor(v) - 1.0, lo_code, hi_code)
        q = jnp.where(x == jnp.inf, float(pad_min), q)
    else:
        q = jnp.clip(jnp.ceil(v) + 1.0, lo_code, hi_code)
        q = jnp.where(x == -jnp.inf, float(pad_max), q)
    return q.astype(dtype)


def _q_plane(plane, mid, s, dim, *, lo_code, hi_code, pad_min, pad_max,
             dtype):
    """Quantize a (2*dim, N) [mins..., maxs...] SoA plane outward."""
    rows = []
    for a in range(dim):
        rows.append(_q_bounds(plane[a], mid[a], s[a], lo_code=lo_code,
                              hi_code=hi_code, pad_min=pad_min,
                              pad_max=pad_max, is_min=True, dtype=dtype))
    for a in range(dim):
        rows.append(_q_bounds(plane[dim + a], mid[a], s[a],
                              lo_code=lo_code, hi_code=hi_code,
                              pad_min=pad_min, pad_max=pad_max,
                              is_min=False, dtype=dtype))
    return jnp.stack(rows)


def quantize_fine(grid: QuantGrid, fine, dim: int) -> jax.Array:
    """(2*dim, NTp) f32 fine tile MBRs -> int16 codes (outward)."""
    return _q_plane(fine, grid.mid, grid.s16, dim, lo_code=I16_LO,
                    hi_code=I16_HI, pad_min=I16_PAD_MIN,
                    pad_max=I16_PAD_MAX, dtype=jnp.int16)


def quantize_coarse(grid: QuantGrid, coarse, dim: int) -> jax.Array:
    """(2*dim, NCp) f32 coarse MBRs -> int32 codes (outward)."""
    return _q_plane(coarse, grid.mid, grid.s32, dim, lo_code=I32_LO,
                    hi_code=I32_HI, pad_min=I32_PAD_MIN,
                    pad_max=I32_PAD_MAX, dtype=jnp.int32)


def quantize_rects(grid: QuantGrid, rsoa,
                   dim: int) -> Tuple[jax.Array, jax.Array]:
    """(2*dim, B) f32 rects -> (int16, int32) outward-rounded codes.

    Rects round outward too (mins down, maxs up): expanding *both*
    sides of the intersect test keeps the quantized candidate set a
    superset of the float32 one.
    """
    r16 = _q_plane(rsoa, grid.mid, grid.s16, dim, lo_code=I16_LO,
                   hi_code=I16_HI, pad_min=I16_PAD_MIN,
                   pad_max=I16_PAD_MAX, dtype=jnp.int16)
    r32 = _q_plane(rsoa, grid.mid, grid.s32, dim, lo_code=I32_LO,
                   hi_code=I32_HI, pad_min=I32_PAD_MIN,
                   pad_max=I32_PAD_MAX, dtype=jnp.int32)
    return r16, r32


# --------------------------------------------------------------------------
# Quantized hierarchical prune (dense reference / XLA building block)
# --------------------------------------------------------------------------

def quantized_prune_mask(
    qfine, qcoarse, r16, r32, qstart, qend, *,
    dim: int = 2, tb: int = TB, tp: int = TP, group: int = COARSE_GROUP,
) -> jax.Array:
    """(B // tb, NTp) bool — quantized coarse∧fine∧slice prune.

    Same contract as ``descent.prune_tiles_pallas`` but over integer
    code planes; by the outward rounding the mask is a superset of the
    f32 prune mask (property-tested), which is all soundness needs.
    """
    ntp = qfine.shape[1]
    B = r16.shape[1]
    gidx = jnp.arange(ntp, dtype=jnp.int32)[None, :]
    ok = (gidx * tp < qend[:, None]) & (gidx * tp + tp > qstart[:, None])
    for a in range(dim):
        ok = ok & (qfine[a][None, :] <= r16[dim + a][:, None])
        ok = ok & (qfine[dim + a][None, :] >= r16[a][:, None])
    cok = jnp.ones((B, qcoarse.shape[1]), dtype=bool)
    for a in range(dim):
        cok = cok & (qcoarse[a][None, :] <= r32[dim + a][:, None])
        cok = cok & (qcoarse[dim + a][None, :] >= r32[a][:, None])
    ok = ok & jnp.repeat(cok, group, axis=1)[:, :ntp]
    return jnp.any(ok.reshape(B // tb, tb, ntp), axis=1)


def compact_ascending(mask: jax.Array, nt: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """Prune mask (NB, >=nt) -> (cand (NB, nt) int32 ascending actives
    then the last active repeated, cnt (NB,) int32).  Same contract as
    ``core.engine.compact_candidates`` (which now delegates here)."""
    active = mask[:, :nt] > 0
    cnt = active.sum(axis=1).astype(jnp.int32)
    j = jnp.arange(nt, dtype=jnp.int32)
    order = jnp.argsort(
        jnp.where(active, j[None, :], nt + j[None, :]), axis=1
    ).astype(jnp.int32)
    last = order[jnp.arange(order.shape[0]), jnp.maximum(cnt - 1, 0)]
    cand = jnp.where(j[None, :] < cnt[:, None], order, last[:, None])
    return cand, cnt


# --------------------------------------------------------------------------
# The megakernel (one pallas_call: prune + compact + double-buffered scan)
# --------------------------------------------------------------------------

def _fused_kernel(qf_ref, qc_ref, rq_ref, q_ref, qse_ref, e_any, *rest,
                  mode: str, kcap: int, dim: int, tp: int):
    if mode == "collect":
        ids_any, o_ref, cnt_ref, ebuf, esem, ibuf, isem = rest
    else:
        o_ref, cnt_ref, ebuf, esem = rest
        ids_any = ibuf = isem = None

    qs = qse_ref[:, 0:1]                    # (TB, 1)
    qe = qse_ref[:, 1:2]

    # ---- phase 1: quantized hierarchical prune (all in VMEM) ----------
    qf = qf_ref[...].astype(jnp.int32)      # (2*dim, NTp) int16 codes
    qc = qc_ref[...]                        # (2*dim, NTp) int32 coarse
    rq = rq_ref[...]                        # (TB, 4*dim) [r16 | r32]
    ntp = qf.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, ntp), 1)
    ok = (lanes * tp < qe) & (lanes * tp + tp > qs)     # (TB, NTp)
    ok = ok & box_hits(qf, rq[:, :2 * dim], dim)
    ok = ok & box_hits(qc, rq[:, 2 * dim:], dim)
    act = jnp.max(ok.astype(jnp.int32), axis=0, keepdims=True) > 0

    # ---- phase 2: worklist size; slots are the actives in ascending
    # order, each found from the previous one by a masked lane min ------
    cnt = jnp.sum(act.astype(jnp.int32))
    cnt_ref[...] = jnp.full(cnt_ref.shape, cnt, jnp.int32)
    n = jnp.minimum(cnt, kcap)

    def next_tile(t):
        """Smallest active tile id > t (``ntp`` when none is left)."""
        return jnp.min(jnp.where(act & (lanes > t), lanes, ntp))

    # ---- phase 3: double-buffered masked scan over the worklist -------
    q = q_ref[...]                          # (TB, 2*dim) exact f32 rects

    def dma(t, slot):
        """The (deterministic) copy descriptors for tile t into buffer
        ``slot`` — rebuilt identically at start and wait time."""
        off = pl.multiple_of(t * tp, tp)
        cps = [pltpu.make_async_copy(
            e_any.at[:, pl.ds(off, tp)], ebuf.at[slot], esem.at[slot])]
        if mode == "collect":
            cps.append(pltpu.make_async_copy(
                ids_any.at[:, pl.ds(off, tp)], ibuf.at[slot],
                isem.at[slot]))
        return cps

    t0 = next_tile(-1)

    @pl.when(n > 0)
    def _first():
        for cp in dma(t0, 0):
            cp.start()

    if mode == "collect":
        o_ref[...] = jnp.full(o_ref.shape, ID_SENTINEL, dtype=jnp.int32)

    def body(k, carry):
        t, acc = carry
        slot = jax.lax.rem(k, 2)
        t_next = next_tile(t)

        @pl.when(k + 1 < n)
        def _next():
            for cp in dma(t_next, 1 - slot):
                cp.start()

        for cp in dma(t, slot):
            cp.wait()
        g = t * tp + jax.lax.broadcasted_iota(jnp.int32, (1, tp), 1)
        hit = (g >= qs) & (g < qe) & box_hits(ebuf[slot], q, dim)
        if mode == "reach":
            acc = acc | jnp.max(hit.astype(jnp.int32), axis=1,
                                keepdims=True)
        elif mode == "count":
            acc = acc + jnp.sum(hit.astype(jnp.int32), axis=1,
                                keepdims=True)
        else:
            vals = jnp.where(hit, ibuf[slot], ID_SENTINEL)
            o_ref[:, pl.ds(pl.multiple_of(k * tp, tp), tp)] = vals
        return t_next, acc

    _, acc = jax.lax.fori_loop(
        0, n, body, (t0, jnp.zeros((q.shape[0], 1), jnp.int32)))
    if mode != "collect":
        o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=(
    "mode", "kcap", "nt", "dim", "interpret", "tb", "tp", "group"))
def fused_serve_pallas(
    qfine: jax.Array,         # (2*dim, NTp) int16 quantized fine MBRs
    qcoarse: jax.Array,       # (2*dim, NTp // group) int32 quantized
    entries_soa: jax.Array,   # (2*dim, P) float32 arena (stays in HBM)
    ids_soa: jax.Array,       # (1, P) int32 payload ids (collect mode)
    r16: jax.Array,           # (2*dim, B) int16 quantized rects
    r32: jax.Array,           # (2*dim, B) int32 quantized rects
    rects_soa: jax.Array,     # (2*dim, B) float32 exact rects
    qstart: jax.Array,        # (B,) int32
    qend: jax.Array,          # (B,) int32
    *,
    mode: str,                # "reach" | "count" | "collect"
    kcap: int,                # worklist capacity (tiles per query tile)
    nt: int,                  # true fine tile count
    dim: int = 2,
    interpret: bool = False,
    tb: int = TB,
    tp: int = TP,
    group: int = COARSE_GROUP,
) -> Tuple[jax.Array, jax.Array]:
    """Single-launch fused serve.  Returns ``(out, cnt)``:

    * ``out`` — mode reach/count: (B,) int32 hits / exact counts;
      mode collect: (B, kcap*tp) int32 ids-or-sentinel matrix;
    * ``cnt`` — (B // tb,) int32 true candidate-tile counts.  Any
      value > ``kcap`` means the scan was truncated and the caller must
      re-run at a larger capacity (the engine's ratchet).

    Block layout (TPU (8, 128) rule): the pyramid planes are full-array
    VMEM blocks, the coarse plane repeated to fine-tile width so both
    prune tests share one lane axis; the query tile sits on sublanes —
    ``(tb, 4*dim)`` int32 quantized rect rows (int16 codes widened
    exactly), ``(tb, 2*dim)`` f32 rect rows, ``(tb, 2)`` arena slices —
    and the per-query outputs are ``(tb, 1)`` columns.
    """
    two_dim, P = entries_soa.shape
    _, B = rects_soa.shape
    ntp = qfine.shape[1]
    assert two_dim == 2 * dim
    assert P % tp == 0 and B % tb == 0, (P, B)
    assert ntp % group == 0 and qcoarse.shape == (two_dim, ntp // group)
    assert mode in ("reach", "count", "collect"), mode
    nb = B // tb
    kcap = max(int(kcap), 1)

    rq = jnp.concatenate([r16.astype(jnp.int32), r32], axis=0).T
    in_specs = [
        pl.BlockSpec((two_dim, ntp), lambda i: (0, 0)),
        pl.BlockSpec((two_dim, ntp), lambda i: (0, 0)),
        pl.BlockSpec((tb, 2 * two_dim), lambda i: (i, 0)),
        *query_specs(tb, two_dim, lambda i: i),
        pl.BlockSpec(memory_space=pl.ANY),              # entry arena
    ]
    args = [qfine, jnp.repeat(qcoarse, group, axis=1), rq,
            *query_rows(rects_soa, qstart, qend), entries_soa]
    scratch = [
        pltpu.VMEM((2, two_dim, tp), jnp.float32),      # tile buffers
        pltpu.SemaphoreType.DMA((2,)),
    ]
    if mode == "collect":
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        args.append(ids_soa)
        scratch += [pltpu.VMEM((2, 1, tp), jnp.int32),
                    pltpu.SemaphoreType.DMA((2,))]
        out_spec = pl.BlockSpec((tb, kcap * tp), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((B, kcap * tp), jnp.int32)
    else:
        out_spec = pl.BlockSpec((tb, 1), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((B, 1), jnp.int32)

    out, cnt = pl.pallas_call(
        functools.partial(_fused_kernel, mode=mode, kcap=kcap, dim=dim,
                          tp=tp),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=[out_spec, pl.BlockSpec((tb, 1), lambda i: (i, 0))],
        out_shape=[out_shape, jax.ShapeDtypeStruct((B, 1), jnp.int32)],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*args)
    if mode != "collect":
        out = out[:, 0]
    return out, cnt[::tb, 0]


# --------------------------------------------------------------------------
# Fused XLA implementation (oracle for the kernel; serving path on CPU)
# --------------------------------------------------------------------------

def fused_serve_xla(
    qfine, qcoarse, entries_soa, ids_soa, r16, r32, rects_soa,
    qstart, qend, *, mode: str, kcap: int, nt: int, dim: int = 2,
    tb: int = TB, tp: int = TP, group: int = COARSE_GROUP,
) -> Tuple[jax.Array, jax.Array]:
    """Same contract as :func:`fused_serve_pallas`, as one fused XLA
    program: dense quantized prune → ascending compaction → gathered
    leaf-tile scan.  Bit-identical to the megakernel (tested)."""
    B = rects_soa.shape[1]
    nb = B // tb
    kcap = max(int(kcap), 1)
    mask = quantized_prune_mask(qfine, qcoarse, r16, r32, qstart, qend,
                                dim=dim, tb=tb, tp=tp, group=group)
    cand, cnt = compact_ascending(mask, nt)
    if kcap <= nt:                                       # (nb, kcap)
        ck = cand[:, :kcap]
    else:                    # capacity beyond the tile count: repeat the
        ck = jnp.concatenate(  # last column; the live mask inerts it
            [cand, jnp.broadcast_to(cand[:, -1:], (nb, kcap - nt))],
            axis=1)
    live = (jnp.arange(kcap, dtype=jnp.int32)[None, :]
            < cnt[:, None])                              # (nb, kcap)
    # gather the candidate leaf tiles: global entry index per lane
    g = (ck[:, :, None] * tp
         + jnp.arange(tp, dtype=jnp.int32)[None, None, :]
         ).reshape(nb, kcap * tp)                        # (nb, K*tp)
    tiles = jnp.take(entries_soa, g, axis=1)             # (2*dim, nb, K*tp)
    qs = qstart.reshape(nb, tb)[:, :, None]
    qe = qend.reshape(nb, tb)[:, :, None]
    q = rects_soa.reshape(2 * dim, nb, tb)
    hit = (g[:, None, :] >= qs) & (g[:, None, :] < qe)   # (nb, tb, K*tp)
    for a in range(dim):
        hit = hit & (tiles[a][:, None, :] <= q[dim + a][:, :, None])
        hit = hit & (tiles[dim + a][:, None, :] >= q[a][:, :, None])
    hit = hit & jnp.repeat(live, tp, axis=1)[:, None, :]
    if mode == "reach":
        out = jnp.any(hit, axis=2).astype(jnp.int32).reshape(B)
    elif mode == "count":
        out = jnp.sum(hit, axis=2).astype(jnp.int32).reshape(B)
    elif mode == "collect":
        ids = jnp.take(ids_soa[0], g, axis=0)            # (nb, K*tp)
        out = jnp.where(hit, ids[:, None, :], ID_SENTINEL).reshape(
            B, kcap * tp)
    else:
        raise ValueError(f"unknown fused mode {mode!r}")
    return out, cnt
