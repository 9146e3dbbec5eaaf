"""Device-resident RangeReach query engine (compile-once serving).

The paper's pitch is that a 2DReach query "reduces to a single 2D R-tree
lookup" — but a lookup that round-trips through host NumPy per batch
(pointer gather on CPU, forest re-transposed to SoA per call, every leaf
scanned) forfeits the reduction.  :class:`QueryEngine` uploads a built
:class:`~repro.core.two_d_reach.TwoDReachIndex` to the accelerator
**once** and answers ``query_batch`` entirely on device:

The default serving path is the **fused megakernel**
(:mod:`repro.kernels.range_query.fused`): ONE dispatch per batch that
routes vertex→tree in-trace, prunes against *quantized* tile-MBR planes
(int16 fine / int32 coarse, outward-rounded so the candidate set
provably contains the f32 truth), compacts the surviving tiles into an
in-kernel worklist, and scans them with the exact f32 leaf predicate —
the boolean / count / collect epilogues share the trace via a mode
flag, so ``query_batch`` / ``count_batch`` / ``collect_batch`` all ride
one kernel with no prune→host→scan round trip.  Batches are padded to
power-of-two buckets by an on-device :class:`DevicePadder` (donated
per-bucket buffers, no host re-stack), and the candidate capacity K is
a monotone high-water mark: an overflowing batch re-runs once at the
ratcheted capacity, so steady-state serving recompiles nothing —
asserted by tests via jit cache-size introspection.

The pre-fusion **two-phase** path is retained in full — reachable via
``path="two_phase"`` or the ``*_two_phase`` methods — as the oracle the
fused path is bit-compared against, as the
:class:`~repro.resilience.engine.ResilientEngine` degradation target,
and as the host of the polygon class (whose half-plane scan is not
fused):

1. **fused pointer lookup** — vertex→tree inside the jit: a plain
   gather for the base/comp variants, or the Pointer variant's
   bit-vector + rank structure evaluated with an in-jit SWAR popcount;
   spatial-sink queries (Alg. 2's special case) fuse to a point-in-rect
   test in the same trace;
2. **hierarchical prune** — the Pallas ``prune_tiles`` kernel ANDs each
   query rect against internal-level tile MBRs (coarse gate + fine
   test, see :mod:`repro.kernels.range_query.descent`) to decide which
   leaf tiles each query tile actually needs;
3. **masked descent scan** — the scalar-prefetch ``descent_scan``
   kernel visits only the compacted candidate tiles, so work scales
   with the query's R-tree footprint instead of the arena size.

Exactness never rests on the pruning (quantized or f32): the scan
re-masks by arena slice and exact box test, so both paths are
bit-identical to the ``query_host`` oracle (scanning an extra tile is
an idempotent OR with no new hits).

The upload path is factored into two reusable pieces so the sharded
cluster engine (:mod:`repro.cluster`) serves the same structures:

* :class:`PointerSide` — the replicated vertex→tree lookup arrays plus
  the fused in-jit routing (lookup + Alg. 2 forced answers);
* :class:`TileArena` — one SoA entry arena + tile-MBR pyramid (a shard
  holds one arena; the single-device engine holds the whole forest's).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels.range_query.analytics import (
    ID_SENTINEL,
    collect_scan_pallas,
    count_scan_pallas,
    polygon_scan_pallas,
)
from ..kernels.range_query.descent import (
    build_tile_pyramid,
    descent_scan_pallas,
    prune_tiles_pallas,
)
from ..kernels.range_query.fused import (
    compact_ascending,
    fused_serve_pallas,
    fused_serve_xla,
    make_quant_grid,
    quantize_coarse,
    quantize_fine,
    quantize_rects,
)
from ..kernels.range_query.kernel import TB, TP
from ..kernels.range_query.ops import forest_soa
from ..obs import CounterDict, REGISTRY, span
from ..obs.tracer import TRACER as _TRACER
from ..resilience.faults import fault_point, fault_value
from .polygon import convex_halfplanes, points_in_polygon_region, polygon_bbox
from .two_d_reach import TwoDReachIndex


def _bucket(n: int, lo: int) -> int:
    """Smallest power-of-two >= max(n, lo) (lo itself a power of two)."""
    b = lo
    while b < n:
        b <<= 1
    return b


def _collect_post(mat: jax.Array, *, kc: int):
    """Fused collect postprocess: (B, K*TP) ids-or-sentinel -> the
    ``kc`` smallest ids per row (sentinel sorts last) + exact totals."""
    srt = jnp.sort(mat, axis=1)
    cnt = jnp.sum(mat != ID_SENTINEL, axis=1)
    return srt[:, :kc], cnt


def _popcount32_jnp(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & np.uint32(0x55555555))
    x = (x & np.uint32(0x33333333)) + ((x >> 2) & np.uint32(0x33333333))
    x = (x + (x >> 4)) & np.uint32(0x0F0F0F0F)
    return ((x * np.uint32(0x01010101)) >> 24).astype(jnp.int32)


# --------------------------------------------------------------------------
# Reusable upload pieces (single-device engine + cluster shards)
# --------------------------------------------------------------------------

# Build→serve handoff counters since import.  ``host_uploads`` counts
# arenas built from host arrays (transpose + pyramid + upload);
# ``device_adoptions`` counts arenas adopted zero-copy from a
# ``build_forest_device`` handoff.  Benchmarks and tests assert that
# serving a device-built index — including every DynamicIndex compaction
# swap — bumps only the adoption counter.  The values live in the
# ``repro.obs`` metrics registry (``engine.upload.*``); this dict-shaped
# view keeps the legacy ``UPLOAD_COUNTERS[...]`` surface working.
UPLOAD_COUNTERS = CounterDict(
    "engine.upload.", ("host_uploads", "device_adoptions"))

_SIDE_ARRAYS = ("_coords", "_excluded", "_vertex_tree", "_vertex_comp",
                "_bits", "_rank", "_tree_ptrs")


@jax.tree_util.register_pytree_node_class
class PointerSide:
    """Device-resident vertex→tree lookup side of a 2DReach index.

    Holds the arrays every serving replica needs in full — coords,
    excluded mask, and the variant's pointer structure — and evaluates
    the fused lookup / Alg. 2 routing inside whatever jit traces it.
    In the cluster engine these arrays are *replicated* per device while
    the R-tree arenas shard: pass the mesh's replicated ``sharding``.
    A pytree (arrays as leaves), so a program can take it as an
    argument instead of closing over it.
    """

    def __init__(self, index: TwoDReachIndex, sharding=None):
        self.variant = index.variant
        self.dim = index.forest.dim

        def put(x, dtype=None):
            x = np.asarray(x, dtype)
            return (jnp.asarray(x) if sharding is None
                    else jax.device_put(x, sharding))

        self._coords = put(index.coords, np.float32)
        self._excluded = put(index.excluded)
        self._vertex_tree = self._vertex_comp = None
        self._bits = self._rank = self._tree_ptrs = None
        if self.variant == "pointer":
            self._vertex_comp = put(index.vertex_comp, np.int32)
            self._bits = put(index.bitrank.bits)
            self._rank = put(index.bitrank.rank, np.int32)
            self._tree_ptrs = put(index.tree_ptrs, np.int32)
        else:
            self._vertex_tree = put(index.vertex_tree, np.int32)

    def tree_flatten(self):
        return (tuple(getattr(self, f) for f in _SIDE_ARRAYS),
                (self.variant, self.dim))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        side = object.__new__(cls)
        side.variant, side.dim = aux
        for f, x in zip(_SIDE_ARRAYS, leaves):
            setattr(side, f, x)
        return side

    def lookup(self, us: jax.Array) -> jax.Array:
        """Fused vertex -> tree id (-1: excluded / no tree), in-jit."""
        if self.variant != "pointer":
            return self._vertex_tree[us]
        c = self._vertex_comp[us]
        ok = c >= 0
        cc = jnp.maximum(c, 0)
        w = cc // 32
        b = (cc % 32).astype(jnp.uint32)
        word = self._bits[w]
        member = ((word >> b) & np.uint32(1)) > 0
        below = word & ((np.uint32(1) << b) - np.uint32(1))
        rank = self._rank[w] + _popcount32_jnp(below)
        t = self._tree_ptrs[
            jnp.minimum(rank, self._tree_ptrs.shape[0] - 1)
        ]
        return jnp.where(ok & member, t, -1)

    def route(self, us: jax.Array, rects_soa: jax.Array
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """(tree id, needs-tree-probe mask, Alg. 2 forced answers).

        ``forced`` is the spatial-query special case fused in-trace: an
        excluded (spatial-sink) query vertex answers by its own point
        against the rect, with the same float32 comparisons as host.
        """
        dim = self.dim
        tid = self.lookup(us)
        exc = self._excluded[us]
        valid = (tid >= 0) & ~exc
        pt = self._coords[us]
        inr = jnp.ones(us.shape[0], dtype=bool)
        for a in range(dim):
            inr = inr & (pt[:, a] >= rects_soa[a])
            inr = inr & (pt[:, a] <= rects_soa[dim + a])
        return tid, valid, exc & inr


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["entries", "fine", "coarse", "entry_off"],
    meta_fields=["n_tiles"])
@dataclasses.dataclass(frozen=True)
class TileArena:
    """One uploaded SoA entry arena + its tile-MBR pyramid."""

    entries: jax.Array     # (2*dim, Pp) float32 SoA planes
    fine: jax.Array        # (2*dim, NTp) float32 leaf-tile MBRs
    coarse: jax.Array      # (2*dim, NTp // COARSE_GROUP) float32
    entry_off: jax.Array   # (T+1,) int32 per-tree arena slices
    n_tiles: int           # true fine tile count (Pp // TP)

    @classmethod
    def upload(cls, esoa: np.ndarray, off: np.ndarray,
               dim: int) -> "TileArena":
        UPLOAD_COUNTERS["host_uploads"] += 1
        with span("engine.soa_upload", cat="build",
                  nbytes=int(esoa.nbytes)):
            fine, coarse, nt = build_tile_pyramid(esoa, dim)
            return cls(
                entries=jnp.asarray(esoa),
                fine=jnp.asarray(fine),
                coarse=jnp.asarray(coarse),
                entry_off=jnp.asarray(off, jnp.int32),
                n_tiles=nt,
            )

    @classmethod
    def for_forest(cls, forest, dim: int) -> "TileArena":
        """Arena for a built forest — adopted zero-copy when the forest
        carries a ``build_forest_device`` handoff (the arrays are
        already device-resident in exactly this layout), uploaded from
        the host arrays otherwise."""
        dev = getattr(forest, "device", None)
        if dev is not None:
            UPLOAD_COUNTERS["device_adoptions"] += 1
            return cls(
                entries=dev.entries,
                fine=dev.fine,
                coarse=dev.coarse,
                entry_off=dev.entry_off,
                n_tiles=dev.n_tiles,
            )
        esoa, off = forest_soa(forest)        # cached transposition
        return cls.upload(esoa, off, dim)


class _WithDeviceArrays:
    """A jitted ``fn(dev, *args)`` called as ``fn(*args)`` with the
    engine's device-array pytree ``dev`` as its first argument."""

    def __init__(self, jitted, dev):
        self._jitted = jitted
        self._dev = dev

    def __call__(self, *args, **kw):
        return self._jitted(self._dev, *args, **kw)

    def _cache_size(self) -> int:
        return self._jitted._cache_size()


def compact_candidates(mask: jax.Array, nt: int
                       ) -> Tuple[jax.Array, jax.Array]:
    """Prune mask (NB, >=nt) -> compacted candidate tiles per query tile.

    Returns ``(cand (NB, nt) int32, cnt (NB,) int32)``: active tiles
    first (ascending), then the last active tile repeated so consecutive
    identical block indices elide the scan kernel's DMA.  (Delegates to
    the fused module's :func:`compact_ascending` — one definition shared
    by the two-phase path, the fused XLA path, and the cluster engine.)
    """
    return compact_ascending(mask, nt)


def pad_batch(us: np.ndarray, rects: np.ndarray, dim: int
              ) -> Tuple[int, np.ndarray, np.ndarray]:
    """Pad a host batch to its power-of-two bucket.

    Returns ``(Bb, us_p (Bb,) int32, rsoa (2*dim, Bb) float32)``.
    Padding rects must miss every box regardless of data extent:
    min=+inf / max=-inf fails both halves of the intersect test (a
    finite 1.0/0.0 sentinel would phantom-hit tiles spanning it).
    """
    B = len(us)
    rects = np.asarray(rects, dtype=np.float32).reshape(B, 2 * dim)
    Bb = _bucket(B, TB)
    us_p = np.zeros(Bb, dtype=np.int32)
    us_p[:B] = us
    rsoa = np.empty((2 * dim, Bb), dtype=np.float32)
    rsoa[:dim] = np.inf
    rsoa[dim:] = -np.inf
    rsoa[:, :B] = rects.T
    return Bb, us_p, rsoa


class DevicePadder:
    """Device-resident batch padding — kills the host ``pad_batch``
    re-stack on the serving hot path.

    Keeps, per power-of-two bucket, a pinned host *staging* pair plus a
    donated device buffer pair.  A batch copies only its true-B prefix
    into the staging arrays (no allocation, no tail memset — O(B) host
    work instead of the old full-bucket re-stack), uploads the
    bucket-shaped staging, and the fill jit masks the stale tail inert
    on-device with an iota-vs-live-count compare (``us=0``, rect
    min=+inf / max=-inf), so a larger previous batch can never leak
    rects into a smaller one's padding.  The live count enters the
    trace as a *dynamic* scalar and every array input is bucket-shaped,
    so the fill trace is keyed on the bucket alone — any unseen true B
    inside a warmed bucket is compile-free.  The jit *donates* the
    bucket's device buffers and the outputs are stored back as the next
    batch's donation inputs (serving consumes a batch's rects strictly
    before the same bucket pads again, so the aliasing is safe), which
    lets XLA write each fill into the existing allocation.  The cache
    size feeds the engine's ``n_compiles`` introspection.  ``sharding``
    places the buffers (the cluster engine replicates them over its
    mesh); ``None`` keeps them on the default device.
    """

    def __init__(self, dim: int, sharding=None):
        self.dim = dim
        self._sharding = sharding
        self._bufs: Dict[int, Tuple[jax.Array, jax.Array]] = {}
        self._stage: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

        def fill(us_buf, r_buf, us_stage, r_stage, b):
            Bb = us_buf.shape[0]
            live = jnp.arange(Bb, dtype=jnp.int32) < b
            us_o = jnp.where(live, us_stage, 0)
            inert = jnp.concatenate([
                jnp.full((dim, Bb), jnp.inf, jnp.float32),
                jnp.full((dim, Bb), -jnp.inf, jnp.float32)])
            r_o = jnp.where(live[None, :], r_stage, inert)
            return us_o, r_o

        self._fill = jax.jit(fill, donate_argnums=(0, 1),
                             out_shardings=sharding)

    def _cache_size(self) -> int:
        return self._fill._cache_size()

    def _put(self, x: np.ndarray) -> jax.Array:
        return (jnp.asarray(x) if self._sharding is None
                else jax.device_put(x, self._sharding))

    def pad(self, us: np.ndarray, rects: np.ndarray
            ) -> Tuple[int, jax.Array, jax.Array]:
        """Pad to the pow2 bucket on-device.  Returns ``(Bb, us_dev
        (Bb,) int32, rsoa_dev (2*dim, Bb) float32)`` — same contents as
        ``pad_batch`` would produce, already device-resident."""
        B = len(us)
        Bb = _bucket(B, TB)
        stage = self._stage.get(Bb)
        if stage is None:
            stage = self._stage[Bb] = (
                np.zeros(Bb, np.int32),
                np.zeros((2 * self.dim, Bb), np.float32))
        us_s, r_s = stage
        us_s[:B] = us
        r_s[:, :B] = np.asarray(
            rects, dtype=np.float32).reshape(B, 2 * self.dim).T
        bufs = self._bufs.get(Bb)
        if bufs is None:
            rs0 = np.empty((2 * self.dim, Bb), np.float32)
            rs0[: self.dim] = np.inf
            rs0[self.dim:] = -np.inf
            bufs = (self._put(np.zeros(Bb, np.int32)), self._put(rs0))
        us_b, r_b = self._fill(bufs[0], bufs[1], self._put(us_s),
                               self._put(r_s), np.int32(B))
        self._bufs[Bb] = (us_b, r_b)
        return Bb, us_b, r_b


# --------------------------------------------------------------------------
# Single-device engine
# --------------------------------------------------------------------------

class QueryEngine:
    """Compile-once device engine over a built ``TwoDReachIndex``.

    Parameters
    ----------
    index:     any 2DReach variant (``base`` / ``comp`` / ``pointer``).
    interpret: run the Pallas kernels in interpret mode; ``None`` picks
               real kernels on TPU and interpret elsewhere.
    path:      ``"fused"`` (default) serves reach/count/collect through
               the single-launch fused kernel; ``"two_phase"`` forces
               the retained prune→compact→scan reference path.
    fused_impl: ``"pallas"`` (the megakernel) or ``"xla"`` (the fused
               XLA program, bit-identical); ``None`` picks the
               megakernel on TPU and the XLA program elsewhere (one
               compiled XLA dispatch beats an interpreted kernel on
               CPU).
    """

    def __init__(self, index: TwoDReachIndex,
                 interpret: Optional[bool] = None,
                 path: str = "fused",
                 fused_impl: Optional[str] = None):
        if not isinstance(index, TwoDReachIndex):
            raise TypeError(
                f"QueryEngine serves TwoDReachIndex, got {type(index).__name__}"
            )
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        if path not in ("fused", "two_phase"):
            raise ValueError(f"unknown engine path {path!r}")
        if fused_impl is None:
            fused_impl = "pallas" if jax.default_backend() == "tpu" else "xla"
        if fused_impl not in ("pallas", "xla"):
            raise ValueError(f"unknown fused impl {fused_impl!r}")
        self._interpret = bool(interpret)
        self.path = path
        self._fused_impl = fused_impl
        self.variant = index.variant
        self.dim = index.forest.dim
        self._index = index        # host mirror (KNN exact top-up)

        # ---- one-time upload (or zero-copy adoption) -------------------
        self._side = PointerSide(index)
        self._arena = TileArena.for_forest(index.forest, self.dim)
        self.n_tiles = self._arena.n_tiles

        # host-side routing mirrors + payload-id plane for the analytics
        # classes (count/collect/kNN/polygon, see repro.queries): the id
        # plane rides next to the entry arena (sentinel padding so misses
        # sort last), the excluded/coords mirrors resolve the Alg. 2
        # special case per class
        self._excluded_host = index.excluded
        self._coords_host = index.coords
        Pp = int(self._arena.entries.shape[1])
        ids_row = np.full((1, Pp), ID_SENTINEL, dtype=np.int32)
        ids_row[0, : len(index.forest.entry_ids)] = index.forest.entry_ids
        self._ids_row = jnp.asarray(ids_row)
        ent = index.forest.entries
        self._extent_host = (
            np.concatenate([ent[:, : self.dim].min(0),
                            ent[:, self.dim:].max(0)]).astype(np.float64)
            if len(ent) else None
        )

        # quantized MBR planes for the fused path: int16 fine / int32
        # coarse codes over the arena extent, rounded outward so the
        # quantized candidate set provably contains the f32 truth
        self._grid = make_quant_grid(self._extent_host, self.dim)
        self._qfine = quantize_fine(self._grid, self._arena.fine, self.dim)
        self._qcoarse = quantize_coarse(
            self._grid, self._arena.coarse, self.dim)

        self.stats: Dict[str, float] = {
            "uploads": 1, "batches": 0, "queries": 0,
            "adopted": int(getattr(index.forest, "device", None) is not None),
            "tiles_scanned": 0, "tiles_grid": 0, "tiles_full_scan": 0,
            "fused_reruns": 0,
        }
        # candidate-capacity high-water mark: K only ratchets up, so a
        # smaller batch never traces a new K shape and lifetime scan
        # retraces are bounded by log2(n_tiles) per batch bucket; extra
        # K columns repeat the last candidate tile, whose DMA the
        # pipeline elides
        self._kb_hwm = 1
        self._padder = DevicePadder(self.dim)
        # the device arrays every program reads, passed as an argument:
        # a closed-over array would be embedded as a constant in each
        # compiled program (one index copy per bucket and mode)
        self._dev = {"side": self._side, "arena": self._arena,
                     "qf": self._qfine, "qc": self._qcoarse,
                     "ids": self._ids_row, "grid": self._grid}
        route = self._make_route()
        serve = self._make_routed_serve()

        def fused(dev, us, rects_soa, *, mode, kcap, kc=None):
            qs, qe, pts, exc = route(dev, us)
            return serve(dev, rects_soa, qs, qe, pts, exc, mode=mode,
                         kcap=kcap, kc=kc)

        def bind(fn, **jit_kw):
            return _WithDeviceArrays(jax.jit(fn, **jit_kw), self._dev)

        self._fused = bind(fused, static_argnames=("mode", "kcap", "kc"))
        self._route = bind(route)
        self._fused_routed = bind(
            serve, static_argnames=("mode", "kcap", "kc"))
        self._prepare = bind(self._make_prepare())
        self._scan = bind(self._make_scan(descent_scan_pallas))
        self._count_scan = bind(self._make_scan(count_scan_pallas))
        self._collect_scan = bind(self._make_scan(collect_scan_pallas,
                                                  with_ids=True))
        self._collect_post = jax.jit(_collect_post, static_argnames=("kc",))
        self._polygon_scan = bind(self._make_polygon_scan(),
                                  static_argnames=("ne",))

    # ------------------------------------------------------------------
    # jitted programs over ``dev`` (per-engine, so cache introspection
    # is local)
    # ------------------------------------------------------------------

    def _make_route(self):
        """Vertex -> (arena slice, point, excluded) routing: the
        rect-independent half of the fused trace, also jitted alone so
        the KNN radius-doubling driver hoists it out of its loop."""

        def route(dev, us):
            side, off = dev["side"], dev["arena"].entry_off
            tid = side.lookup(us)
            exc = side._excluded[us]
            valid = (tid >= 0) & ~exc
            t = jnp.maximum(tid, 0)
            qs = jnp.where(valid, off[t], 0)
            qe = jnp.where(valid, off[t + 1], 0)
            return qs, qe, side._coords[us], exc

        return route

    def _make_routed_serve(self):
        """The fused serve body with routing state as explicit inputs:
        quantize rects outward, then one fused prune+compact+scan launch
        (megakernel or the bit-identical XLA program).  Returns
        ``(forced, out, cnt, cnt.max())`` — ``mx > kcap`` means the scan
        truncated and the driver must ratchet and re-run."""
        dim = self.dim
        nt = self.n_tiles
        interpret = self._interpret
        impl = self._fused_impl

        def serve(dev, rects_soa, qs, qe, pts, exc, *, mode, kcap,
                  kc=None):
            inr = jnp.ones(rects_soa.shape[1], dtype=bool)
            for a in range(dim):
                inr = inr & (pts[:, a] >= rects_soa[a])
                inr = inr & (pts[:, a] <= rects_soa[dim + a])
            forced = exc & inr               # Alg. 2 spatial-sink case
            r16, r32 = quantize_rects(dev["grid"], rects_soa, dim)
            args = (dev["qf"], dev["qc"], dev["arena"].entries, dev["ids"],
                    r16, r32, rects_soa, qs, qe)
            if impl == "pallas":
                out, cnt = fused_serve_pallas(
                    *args, mode=mode, kcap=kcap, nt=nt, dim=dim,
                    interpret=interpret)
            else:
                out, cnt = fused_serve_xla(
                    *args, mode=mode, kcap=kcap, nt=nt, dim=dim)
            if mode == "collect" and kc is not None:
                # collect epilogue inside the same trace: top-kc ids +
                # exact totals, so the host never receives the full
                # (Bb, kcap*TP) id matrix and collect stays one dispatch
                out = _collect_post(out, kc=kc)
            return forced, out, cnt, cnt.max()

        return serve

    def _make_prepare(self):
        nt = self.n_tiles
        interpret = self._interpret
        dim = self.dim

        def prepare(dev, us, rects_soa):
            # us (Bb,) int32; rects_soa (2*dim, Bb) f32
            arena = dev["arena"]
            tid, valid, forced = dev["side"].route(us, rects_soa)
            t = jnp.maximum(tid, 0)
            qs = jnp.where(valid, arena.entry_off[t], 0)
            qe = jnp.where(valid, arena.entry_off[t + 1], 0)
            mask = prune_tiles_pallas(
                arena.fine, arena.coarse, rects_soa, qs, qe,
                dim=dim, interpret=interpret,
            )
            cand, cnt = compact_candidates(mask, nt)
            return forced, qs, qe, cand, cnt, cnt.max()

        return prepare

    def _make_scan(self, kernel, with_ids: bool = False):
        """Two-phase leaf scan ``kernel(cand, entries[, ids], rects, qs,
        qe)`` over the arena."""
        dim = self.dim
        interpret = self._interpret

        def scan(dev, cand_k, rects_soa, qs, qe):
            ids = (dev["ids"],) if with_ids else ()
            return kernel(cand_k, dev["arena"].entries, *ids, rects_soa,
                          qs, qe, dim=dim, interpret=interpret)

        return scan

    def _make_polygon_scan(self):
        dim = self.dim
        interpret = self._interpret

        def scan(dev, cand_k, rects_soa, lines_soa, qs, qe, *, ne):
            return polygon_scan_pallas(
                cand_k, dev["arena"].entries, rects_soa, lines_soa, qs, qe,
                ne=ne, dim=dim, interpret=interpret,
            )

        return scan

    # ------------------------------------------------------------------
    # public surface
    # ------------------------------------------------------------------

    @property
    def n_compiles(self) -> int:
        """Distinct (bucketed) shapes traced so far — flat in steady
        state; tests assert it via this introspection hook."""
        return int(
            self._fused._cache_size() + self._route._cache_size()
            + self._fused_routed._cache_size()
            + self._padder._cache_size()
            + self._prepare._cache_size() + self._scan._cache_size()
            + self._count_scan._cache_size()
            + self._collect_scan._cache_size()
            + self._collect_post._cache_size()
            + self._polygon_scan._cache_size()
        )

    def _route_prune(self, us: np.ndarray, rects: np.ndarray):
        """Shared phase 1 for every query class: pad to the batch
        bucket, run the fused route + hierarchical prune, ratchet the
        candidate high-water mark.  Returns ``(Bb, rsoa_dev, forced,
        qs, qe, cand_k)`` with ``cand_k`` already sliced to the K
        bucket."""
        B = len(us)
        fault_point("engine.route_prune", n=B)
        with span("engine.pad_batch", cat="engine"):
            Bb, us_dev, rsoa_dev = self._padder.pad(us, rects)
        with span("engine.route_prune", cat="engine", batch=B):
            forced, qs, qe, cand, cnt, mx = self._prepare(us_dev, rsoa_dev)
            # int(mx) blocks on the device prune, so the span really
            # covers lookup + prune + candidate compaction
            self._kb_hwm = max(
                self._kb_hwm,
                min(_bucket(max(int(mx), 1), 1), self.n_tiles))
        kb = self._kb_hwm
        self.stats["batches"] += 1
        self.stats["queries"] += B
        # tiles_scanned: live candidate tiles (pruning effectiveness);
        # tiles_grid: kernel grid steps incl. bucket padding (actual work
        # — padded steps repeat the last tile, so their DMA is elided)
        self.stats["tiles_scanned"] += int(np.asarray(cnt).sum())
        self.stats["tiles_grid"] += (Bb // TB) * kb
        self.stats["tiles_full_scan"] += (Bb // TB) * self.n_tiles
        return Bb, rsoa_dev, forced, qs, qe, cand[:, :kb]

    def _fused_serve(self, us: np.ndarray, rects: np.ndarray, mode: str,
                     kc=None):
        """One-dispatch serve for reach/count/collect: device pad, then
        the fused route→prune→scan launch at the current capacity
        high-water mark.  ``mx > kcap`` (capacity overflow — the scan
        truncated) ratchets the monotone hwm and re-runs; warmup-only,
        steady state runs exactly once and recompiles nothing.  Returns
        ``(Bb, forced, out)`` — for collect with static ``kc``, ``out``
        is the in-trace ``(top, counts)`` epilogue pair."""
        B = len(us)
        fault_point("engine.route_prune", n=B)
        with span("engine.pad_batch", cat="engine"):
            Bb, us_dev, rsoa_dev = self._padder.pad(us, rects)
        with span("engine.fused", cat="engine", batch=B, mode=mode):
            while True:
                kcap = min(self._kb_hwm, self.n_tiles)
                forced, out, cnt, mx = self._fused(
                    us_dev, rsoa_dev, mode=mode, kcap=kcap, kc=kc)
                # int(mx) blocks on the device, so the span covers the
                # whole fused launch
                mxi = int(mx)
                if mxi <= kcap or kcap >= self.n_tiles:
                    break
                self._kb_hwm = min(_bucket(mxi, 1), self.n_tiles)
                self.stats["fused_reruns"] += 1
        self.stats["batches"] += 1
        self.stats["queries"] += B
        self.stats["tiles_scanned"] += int(np.asarray(cnt).sum())
        self.stats["tiles_grid"] += (Bb // TB) * kcap
        self.stats["tiles_full_scan"] += (Bb // TB) * self.n_tiles
        return Bb, forced, out

    def _obs_batch(self, kind: str, B: int, t0: float) -> None:
        """Gated per-batch registry recording (enabled-only: one
        histogram append + two updates per *batch*, nothing per query)."""
        if not _TRACER.enabled:
            return
        dt_us = (time.perf_counter() - t0) * 1e6
        REGISTRY.histogram("engine.batch_us").record(dt_us)
        REGISTRY.histogram(f"engine.{kind}.query_us").record(dt_us / max(B, 1))
        REGISTRY.counter(f"engine.{kind}.queries").inc(B)
        REGISTRY.gauge("engine.n_compiles").set(self.n_compiles)

    def query_batch(self, us: np.ndarray, rects: np.ndarray) -> np.ndarray:
        """Batched RangeReach, same contract as ``TwoDReachIndex
        .query_batch`` (and bit-identical to it)."""
        us = np.asarray(us, dtype=np.int64)
        B = len(us)
        if B == 0:
            return np.zeros(0, dtype=bool)
        fault_point("engine.query_batch", n=B)
        t0 = time.perf_counter()
        with span("engine.query_batch", cat="engine", n=B):
            if self.path == "fused":
                _, forced, hit = self._fused_serve(us, rects, "reach")
            else:
                _, rsoa_dev, forced, qs, qe, cand_k = self._route_prune(
                    us, rects)
                with span("engine.scan", cat="engine"):
                    hit = self._scan(cand_k, rsoa_dev, qs, qe)
            with span("engine.sync", cat="engine"):
                out = np.asarray(hit).astype(bool) | np.asarray(forced)
        self._obs_batch("reach", B, t0)
        # value point: a kind="corrupt" fault silently flips answers
        # here — the failure the online exactness auditor must catch
        return fault_value("engine.answer", out[:B])

    def query(self, u: int, rect) -> bool:
        return bool(self.query_batch(np.array([u]), np.array([rect]))[0])

    def _with_path(self, path: str, fn, *args):
        prev, self.path = self.path, path
        try:
            return fn(*args)
        finally:
            self.path = prev

    def query_batch_two_phase(self, us, rects) -> np.ndarray:
        """``query_batch`` through the retained two-phase reference path
        (prune → host compaction → descent scan) — the fused path's
        oracle and the ResilientEngine degradation target."""
        return self._with_path("two_phase", self.query_batch, us, rects)

    def count_batch_two_phase(self, us, rects) -> np.ndarray:
        """``count_batch`` through the two-phase reference path."""
        return self._with_path("two_phase", self.count_batch, us, rects)

    def collect_batch_two_phase(self, us, rects, k: int):
        """``collect_batch`` through the two-phase reference path."""
        return self._with_path("two_phase", self.collect_batch,
                               us, rects, k)

    # -- analytics classes (see repro.queries) --------------------------

    def count_batch(self, us: np.ndarray, rects: np.ndarray) -> np.ndarray:
        """Batched RangeCount: (B,) int64 exact number of reachable
        venues intersecting each rect (bit-identical to the host
        ``repro.queries.range_count_host``)."""
        us = np.asarray(us, dtype=np.int64)
        B = len(us)
        if B == 0:
            return np.zeros(0, dtype=np.int64)
        t0 = time.perf_counter()
        with span("engine.count_batch", cat="engine", n=B):
            if self.path == "fused":
                _, forced, counts = self._fused_serve(us, rects, "count")
            else:
                _, rsoa_dev, forced, qs, qe, cand_k = self._route_prune(
                    us, rects)
                with span("engine.scan", cat="engine"):
                    counts = self._count_scan(cand_k, rsoa_dev, qs, qe)
            # forced: an excluded (spatial-sink) query vertex reaches
            # exactly itself — its tree probe counted nothing (empty
            # slice)
            with span("engine.sync", cat="engine"):
                out = (np.asarray(counts).astype(np.int64)
                       + np.asarray(forced).astype(np.int64))
        self._obs_batch("count", B, t0)
        return out[:B]

    def collect_batch(self, us: np.ndarray, rects: np.ndarray, k: int):
        """Batched RangeCollect: the K smallest reachable venue ids in
        each rect + exact totals and overflow flags — see
        ``repro.queries.CollectResult`` (bit-identical to host)."""
        from ..queries.program import CollectResult  # deferred: no cycle

        us = np.asarray(us, dtype=np.int64)
        B = len(us)
        k = int(k)
        if k < 1:
            raise ValueError(f"collect needs k >= 1, got {k}")
        if B == 0:
            return CollectResult(
                ids=np.zeros((0, k), np.int32),
                counts=np.zeros(0, np.int64),
                overflow=np.zeros(0, bool),
            )
        t0 = time.perf_counter()
        with span("engine.collect_batch", cat="engine", n=B):
            if self.path == "fused":
                _, forced, out = self._fused_serve(
                    us, rects, "collect", kc=_bucket(k, 1))
                top, cnt = out
            else:
                _, rsoa_dev, forced, qs, qe, cand_k = self._route_prune(
                    us, rects)
                with span("engine.scan", cat="engine"):
                    mat = self._collect_scan(cand_k, rsoa_dev, qs, qe)
                    top, cnt = self._collect_post(mat, kc=_bucket(k, 1))
        self._obs_batch("collect", B, t0)
        top = np.asarray(top)[:B]
        counts = np.asarray(cnt).astype(np.int64)[:B]
        ids = np.full((B, k), ID_SENTINEL, dtype=np.int32)
        take = min(k, top.shape[1])
        ids[:, :take] = top[:, :take]
        ids[ids == ID_SENTINEL] = -1
        exc = self._excluded_host[us]
        if exc.any():
            hit = np.nonzero(exc & np.asarray(forced)[:B])[0]
            ids[hit, 0] = us[hit]
            counts[hit] = 1
        return CollectResult(ids=ids, counts=counts, overflow=counts > k)

    def knn_batch(self, us: np.ndarray, points: np.ndarray, k: int):
        """Batched KNNReach via the device radius-doubling driver over
        RangeCount/RangeCollect (see ``repro.queries.knn``); results are
        the exact (dist², id)-ordered k nearest reachable venues,
        bit-identical to the host best-first descent."""
        from ..queries.knn import knn_radius_doubling  # deferred: no cycle

        with span("engine.knn_batch", cat="engine", n=len(us), k=k):
            return knn_radius_doubling(self, us, points, k)

    def polygon_batch(self, us: np.ndarray, polygons) -> np.ndarray:
        """Batched convex-polygon RangeReach: the half-plane postfilter
        runs inside the leaf-scan kernel (bbox prune + canonical f32
        region test; bit-identical to host)."""
        us = np.asarray(us, dtype=np.int64)
        B = len(us)
        if B == 0:
            return np.zeros(0, dtype=bool)
        if len(polygons) != B:
            raise ValueError(f"{len(polygons)} polygons for {B} queries")
        t0 = time.perf_counter()
        with span("engine.polygon_batch", cat="engine", n=B):
            bboxes = np.stack([polygon_bbox(p) for p in polygons])
            ne = max(len(np.asarray(p).reshape(-1, 2)) for p in polygons)
            neb = _bucket(ne, 4)
            hps = np.stack(
                [convex_halfplanes(p, pad_to=neb) for p in polygons])
            Bb, rsoa_dev, _, qs, qe, cand_k = self._route_prune(us, bboxes)
            # (B, 3, neb) -> (3*neb, Bb); padded batch lanes get inert
            # half-planes (A=B=0, C=+inf) to match their impossible rects
            lines = np.zeros((3 * neb, Bb), dtype=np.float32)
            lines[2 * neb:] = np.inf
            lines[:, :B] = hps.transpose(1, 2, 0).reshape(3 * neb, B)
            with span("engine.scan", cat="engine"):
                hit = self._polygon_scan(cand_k, rsoa_dev,
                                         jnp.asarray(lines),
                                         qs, qe, ne=neb)
            with span("engine.sync", cat="engine"):
                out = np.asarray(hit)[:B] > 0
        self._obs_batch("polygon", B, t0)
        exc = self._excluded_host[us]
        if exc.any():
            for i in np.nonzero(exc)[0]:
                out[i] = bool(points_in_polygon_region(
                    self._coords_host[us[i]][None], bboxes[i], hps[i])[0])
        return out


def _unsupported_msg(index, what: str) -> str:
    name = type(index).__name__
    method = getattr(index, "method", None) or getattr(index, "variant", None)
    via = f" (method {method!r})" if isinstance(method, str) else ""
    return (
        f"no {what} for {name}{via}: device/cluster serving supports the "
        f"2DReach variants only (2dreach, 2dreach-comp, 2dreach-pointer)"
    )


def engine_for(index, interpret: Optional[bool] = None,
               required: bool = False):
    """Memoised ``QueryEngine`` for a built 2DReach index (one upload per
    index instance).

    Supported pairings: any :class:`TwoDReachIndex` variant (``base`` /
    ``comp`` / ``pointer``), from either build backend —
    ``build_2dreach(backend="host")`` uploads its arrays here once;
    ``backend="device"`` indexes are *adopted* zero-copy (the build left
    the serving arrays on device; see ``UPLOAD_COUNTERS``).  For index
    types the device engine does not serve (3DReach, GeoReach, anything
    without a 2D forest), returns ``None`` so callers can fall back to
    the host path — or, with ``required=True``, raises a ``ValueError``
    naming the unsupported index/method (instead of the caller tripping
    an ``AttributeError`` deep inside the engine).  An explicit
    ``interpret`` that disagrees with the memoised engine's mode
    rebuilds rather than silently returning the wrong kernel mode."""
    if not isinstance(index, TwoDReachIndex):
        if required:
            raise ValueError(_unsupported_msg(index, "device QueryEngine"))
        return None
    eng = getattr(index, "_device_engine", None)
    if eng is None or (
        interpret is not None and eng._interpret != bool(interpret)
    ):
        eng = QueryEngine(index, interpret=interpret)
        index._device_engine = eng
    return eng
