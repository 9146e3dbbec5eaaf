"""Sharded multi-device RangeReach serving over a partitioned forest.

:class:`ShardedEngine` is the cluster-scale sibling of the single-device
:class:`~repro.core.engine.QueryEngine`.  The 2DReach forest is
partitioned by tree id (size-balanced bin packing over per-tree entry
counts, :mod:`repro.cluster.partition`), one ``QueryEngine``-style SoA
arena + tile pyramid is uploaded **per shard** (stacked and sharded over
the mesh's ``data`` axis), and the vertex→tree pointer arrays are
replicated on every device.  ``query_batch`` runs as **one**
``shard_map``-ed collective program (the fused path, mirroring the
single-device :mod:`repro.kernels.range_query.fused` megakernel): every
device routes the replicated batch, masks it to the queries whose trees
live on its shards, runs the quantized-plane fused prune+scan per local
shard, and the per-query hits ``psum``-OR-reduce across the mesh in the
same trace that ``pmax``-es the candidate max — no prune→host→scan
round trip, one dispatch per batch per capacity bucket (the capacity is
a monotone high-water mark: an overflowing batch ratchets and re-runs
once; steady state runs exactly once).  The pre-fusion two-phase
structure (separate route+prune and scan ``shard_map`` jits with a host
bucket step between them) is retained as ``query_batch_two_phase`` —
the reference the fused program is bit-compared against:

1. **route + prune** — every device evaluates the fused pointer lookup
   for the whole (replicated) batch, masks it down to the queries whose
   tree lives on one of its shards (everyone else gets an empty arena
   slice, so the kernels do no work for them), and runs the Pallas
   hierarchical prune against its own tile pyramid;
2. **masked scan** — after a host-side power-of-two bucket of the global
   candidate max (``pmax`` across shards, so every device traces the
   same K), each device runs the scalar-prefetch descent scan over its
   own arena and the per-query hits ``OR``-reduce across the mesh
   (``psum`` of 0/1 ints).

Every query's tree lives on exactly one shard and that shard's arena
holds exactly the tree's entries (same boxes, same slice contents), so
answers are **bit-identical** to ``query_host`` — the same guarantee the
single-device engine gives, asserted across shard counts in tests.

More shards than devices is legal (and how single-host tests exercise
the 8-shard layout): each device serves ``n_shards / n_devices`` stacked
shards with an unrolled loop inside the same trace, so the program is
identical SPMD everywhere and steady state still recompiles nothing.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..compat import shard_map
from ..core.engine import (
    DevicePadder,
    PointerSide,
    _bucket,
    _unsupported_msg,
    compact_candidates,
    pad_batch,
)
from ..core.two_d_reach import TwoDReachIndex
from ..distributed.sharding import index_shard_specs
from ..kernels.range_query.descent import (
    descent_scan_pallas,
    prune_tiles_pallas,
)
from ..kernels.range_query.fused import (
    fused_serve_pallas,
    fused_serve_xla,
    make_quant_grid,
    quantize_coarse,
    quantize_fine,
    quantize_rects,
)
from ..kernels.range_query.kernel import TB
from ..launch.mesh import make_shard_mesh
from ..obs import REGISTRY, span
from ..obs.tracer import TRACER as _TRACER
from ..resilience.faults import fault_point
from .partition import partition_forest, shard_arenas

_AXIS = "data"


def _devices_for(n_shards: int, n_avail: int) -> int:
    """Largest device count <= n_avail that divides n_shards evenly."""
    for d in range(min(n_shards, n_avail), 0, -1):
        if n_shards % d == 0:
            return d
    return 1


def fused_program(mesh, *, shards_per_dev: int, nt: int, dim: int,
                  kcap: int, impl: str, interpret: bool):
    """The single collective serving program at one static candidate
    capacity: replicated route + rect quantization, per-local-shard
    fused prune+compact+scan, and the cross-shard ``psum`` OR-reduce and
    ``pmax`` capacity check — all in ONE ``shard_map``-ed jit,
    collapsing the old two-dispatch (+ host bucket sync) round.

    Every array is an argument — ``(side, grid, qfine, qcoarse,
    entries, tree_shard, tree_qs, tree_qe, us, rsoa)``, the arena stacks
    sharded over the mesh's ``data`` axis and the rest replicated — so
    the program can be lowered from shapes alone (the TPU compile
    rehearsal does, on a described mesh)."""
    L = shards_per_dev

    def fused(side, grid, qfine, qcoarse, entries, tshard, tqs, tqe, us,
              rsoa):
        # qfine/qcoarse/entries: (L, ...) local shard stacks;
        # us/rsoa replicated.  Everything below the routing runs
        # against local shards only.
        tid, valid, forced = side.route(us, rsoa)
        t = jnp.maximum(tid, 0)
        own = jnp.where(valid, tshard[t], -1)
        r16, r32 = quantize_rects(grid, rsoa, dim)
        first = jax.lax.axis_index(_AXIS) * L
        dummy_ids = jnp.zeros((1, entries.shape[-1]), jnp.int32)
        hit = jnp.zeros((rsoa.shape[1],), jnp.int32)
        cnts = []
        for l in range(L):
            mine = own == first + l
            qs = jnp.where(mine, tqs[t], 0)
            qe = jnp.where(mine, tqe[t], 0)
            if impl == "pallas":
                out, cnt = fused_serve_pallas(
                    qfine[l], qcoarse[l], entries[l], dummy_ids,
                    r16, r32, rsoa, qs, qe, mode="reach", kcap=kcap,
                    nt=nt, dim=dim, interpret=interpret)
            else:
                out, cnt = fused_serve_xla(
                    qfine[l], qcoarse[l], entries[l], dummy_ids,
                    r16, r32, rsoa, qs, qe, mode="reach", kcap=kcap,
                    nt=nt, dim=dim)
            hit = hit | out
            cnts.append(cnt)
        cnt = jnp.stack(cnts)
        mx = jax.lax.pmax(cnt.max(), _AXIS)
        # OR-reduce across shards: hits are 0/1 and each query's
        # tree lives on exactly one shard, so a sum is an OR
        return forced, own, jax.lax.psum(hit, _AXIS), cnt, mx

    return jax.jit(shard_map(
        fused, mesh,
        in_specs=(P(), P(), P(_AXIS), P(_AXIS), P(_AXIS), P(), P(), P(),
                  P(), P()),
        out_specs=(P(), P(), P(), P(_AXIS), P()),
    ))


class ShardedEngine:
    """Compile-once sharded engine over a built ``TwoDReachIndex``.

    Parameters
    ----------
    index:     any 2DReach variant (``base`` / ``comp`` / ``pointer``).
    n_shards:  forest partitions; defaults to the local device count.
               May exceed it — shards then stack per device.
    mesh:      1-D mesh with a ``data`` axis; ``None`` builds one over
               the largest device count that divides ``n_shards``.
    interpret: Pallas interpret mode; ``None`` picks real kernels on
               TPU and interpret elsewhere.
    """

    def __init__(self, index: TwoDReachIndex,
                 n_shards: Optional[int] = None,
                 mesh=None,
                 interpret: Optional[bool] = None):
        if not isinstance(index, TwoDReachIndex):
            raise ValueError(_unsupported_msg(index, "cluster ShardedEngine"))
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self._interpret = bool(interpret)
        self.variant = index.variant
        self.dim = index.forest.dim

        if n_shards is None:
            n_shards = (mesh.shape[_AXIS] if mesh is not None
                        else len(jax.devices()))
        n_shards = int(n_shards)
        if mesh is None:
            mesh = make_shard_mesh(_devices_for(n_shards, len(jax.devices())))
        n_dev = mesh.shape[_AXIS]
        if n_shards % n_dev:
            raise ValueError(
                f"n_shards={n_shards} must be a multiple of the mesh's "
                f"{_AXIS} axis size {n_dev}")
        self.mesh = mesh
        self.n_shards = n_shards
        self._shards_per_dev = n_shards // n_dev

        # ---- partition + one-time sharded upload -----------------------
        self.partition = partition_forest(index.forest, n_shards)
        entries, fine, coarse, nt = shard_arenas(index.forest, self.partition)
        self.n_tiles = nt                       # per shard, uniform
        specs = index_shard_specs(_AXIS)

        def put(x, spec):
            return jax.device_put(x, NamedSharding(mesh, spec))

        self._entries = put(entries, specs["entries"])
        self._fine = put(fine, specs["fine"])
        self._coarse = put(coarse, specs["coarse"])
        # quantized MBR planes for the fused collective program: one
        # grid over the whole forest extent (soundness only needs the
        # rounding to be outward; sharing the grid keeps the replicated
        # rect quantization identical on every device)
        ent = index.forest.entries
        replicated = NamedSharding(mesh, P())
        self._grid = jax.device_put(make_quant_grid(
            np.concatenate([ent[:, : self.dim].min(0),
                            ent[:, self.dim:].max(0)]).astype(np.float64)
            if len(ent) else None,
            self.dim), replicated)
        self._qfine = put(
            jax.vmap(lambda p: quantize_fine(self._grid, p, self.dim))(
                jnp.asarray(fine)), specs["fine"])
        self._qcoarse = put(
            jax.vmap(lambda p: quantize_coarse(self._grid, p, self.dim))(
                jnp.asarray(coarse)), specs["coarse"])
        # replicated routing: tree -> (owning shard, local arena slice),
        # passed to the shard_map programs as arguments
        self._routing = tuple(
            put(np.asarray(getattr(self.partition, k)), specs[k])
            for k in ("tree_shard", "tree_qs", "tree_qe"))
        self._side = PointerSide(index, sharding=replicated)

        self.stats: Dict[str, float] = {
            "uploads": 1, "batches": 0, "queries": 0,
            "adopted": int(getattr(index.forest, "device", None) is not None),
            "tiles_scanned": 0, "tiles_grid": 0, "tiles_full_scan": 0,
            "fused_reruns": 0,
        }
        self.shard_queries = np.zeros(n_shards, dtype=np.int64)
        # per-shard hit counters ride next to the query routing counts:
        # together they are the load signal the future query-log-driven
        # repartitioner consumes (queries = routing pressure, hits =
        # result pressure)
        self.shard_hits = np.zeros(n_shards, dtype=np.int64)
        # host-side mirrors for query-log classification/routing: the
        # structured log records (vertex class, shard) per served query
        self._excluded_host = index.excluded
        self._lookup_tree_host = index.lookup_tree
        # candidate-capacity high-water mark: K only ever ratchets up, so
        # a smaller batch never traces a new K shape and lifetime scan
        # retraces are bounded by log2(n_tiles) per batch bucket.  A
        # regrouped frontend flush (deadline-or-full boundaries are
        # timing-dependent) can still ratchet once if a new query-tile
        # window's candidate union crosses the warmed power-of-two
        # bucket; after that the mark covers it for good
        self._kb_hwm = 1
        self._fused_impl = ("pallas" if jax.default_backend() == "tpu"
                            else "xla")
        self._padder = DevicePadder(self.dim, sharding=replicated)
        # fused collective programs, memoised per static capacity —
        # shard_map cannot take static kwargs, so each ratcheted kcap
        # gets its own program object (bounded: the hwm is monotone
        # pow2, so at most log2(n_tiles) of these ever exist)
        self._fused_progs: Dict[int, object] = {}
        self._prepare = jax.jit(self._make_prepare())
        self._scan = jax.jit(self._make_scan())

    # ------------------------------------------------------------------
    # shard_map-ed jit closures
    # ------------------------------------------------------------------

    def _make_prepare(self):
        dim = self.dim
        interpret = self._interpret
        L, nt = self._shards_per_dev, self.n_tiles

        def prepare(side, fine, coarse, tshard, tqs, tqe, us, rsoa):
            # fine/coarse: (L, 2*dim, ·) local shard stack; us/rsoa
            # replicated.  Routing is replicated compute (identical on
            # every device); only the prune runs against local pyramids.
            tid, valid, forced = side.route(us, rsoa)
            t = jnp.maximum(tid, 0)
            own = jnp.where(valid, tshard[t], -1)   # replicated routing
            first = jax.lax.axis_index(_AXIS) * L
            qs_l, qe_l, cand_l, cnt_l = [], [], [], []
            for l in range(L):
                mine = own == first + l
                qs = jnp.where(mine, tqs[t], 0)
                qe = jnp.where(mine, tqe[t], 0)
                mask = prune_tiles_pallas(
                    fine[l], coarse[l], rsoa, qs, qe,
                    dim=dim, interpret=interpret,
                )
                cand, cnt = compact_candidates(mask, nt)
                qs_l.append(qs)
                qe_l.append(qe)
                cand_l.append(cand)
                cnt_l.append(cnt)
            cnt = jnp.stack(cnt_l)
            mx = jax.lax.pmax(cnt.max(), _AXIS)
            return (forced, own, jnp.stack(qs_l), jnp.stack(qe_l),
                    jnp.stack(cand_l), cnt, mx)

        return shard_map(
            prepare, self.mesh,
            in_specs=(P(), P(_AXIS), P(_AXIS), P(), P(), P(), P(), P()),
            out_specs=(P(), P(), P(_AXIS), P(_AXIS), P(_AXIS),
                       P(_AXIS), P()),
        )

    def _make_scan(self):
        dim, interpret = self.dim, self._interpret
        L = self._shards_per_dev

        def scan(entries, cand, qs, qe, rsoa):
            # entries (L, 2*dim, Pp); cand (L, NB, K); qs/qe (L, Bb)
            hit = jnp.zeros((rsoa.shape[1],), jnp.int32)
            for l in range(L):
                hit = hit | descent_scan_pallas(
                    cand[l], entries[l], rsoa, qs[l], qe[l],
                    dim=dim, interpret=interpret,
                )
            # OR-reduce across shards: hits are 0/1 and each query's
            # tree lives on exactly one shard, so a sum is an OR
            return jax.lax.psum(hit, _AXIS)

        return shard_map(
            scan, self.mesh,
            in_specs=(P(_AXIS), P(_AXIS), P(_AXIS), P(_AXIS), P()),
            out_specs=P(),
        )

    def _fused_prog(self, kcap: int):
        """The collective serving program at one static capacity,
        memoised (see :func:`fused_program`)."""
        prog = self._fused_progs.get(kcap)
        if prog is None:
            prog = self._fused_progs[kcap] = fused_program(
                self.mesh, shards_per_dev=self._shards_per_dev,
                nt=self.n_tiles, dim=self.dim, kcap=kcap,
                impl=self._fused_impl, interpret=self._interpret)
        return prog

    # ------------------------------------------------------------------
    # public surface
    # ------------------------------------------------------------------

    @property
    def n_compiles(self) -> int:
        """Distinct (bucketed) shapes traced so far — flat in steady
        state; tests assert it via this introspection hook."""
        return int(
            self._prepare._cache_size() + self._scan._cache_size()
            + self._padder._cache_size()
            + sum(p._cache_size() for p in self._fused_progs.values())
        )

    def arena_devices(self) -> list:
        """The device holding each shard's arena, by shard id."""
        held = {}
        for piece in self._entries.addressable_shards:
            for s in range(*piece.index[0].indices(self.n_shards)):
                held[s] = piece.device
        return [held[s] for s in range(self.n_shards)]

    def shard_of(self, us: np.ndarray) -> np.ndarray:
        """Host-side vertex -> owning shard (-1: excluded / no tree) —
        the routing key the structured query log records."""
        t = np.asarray(self._lookup_tree_host(np.asarray(us, np.int64)))
        out = np.full(len(t), -1, dtype=np.int64)
        ok = t >= 0
        out[ok] = self.partition.tree_shard[t[ok]]
        return out

    def _finish_batch(self, B, Bb, kb, forced, own, hit, cnt, t0):
        """Shared batch epilogue (fused + two-phase): stats, sync,
        per-shard routing/hit counters, gated registry recording."""
        S = self.n_shards
        self.stats["batches"] += 1
        self.stats["queries"] += B
        self.stats["tiles_scanned"] += int(np.asarray(cnt).sum())
        self.stats["tiles_grid"] += (Bb // TB) * kb * S
        self.stats["tiles_full_scan"] += (Bb // TB) * self.n_tiles * S
        with span("cluster.sync", cat="cluster"):
            # routing stats over the *real* lanes only (padding
            # reuses vertex 0, which routes to a real shard but
            # answers nothing)
            own_b = np.asarray(own)[:B]
            out = (np.asarray(hit) > 0) | np.asarray(forced)
        routed = own_b >= 0
        self.shard_queries += np.bincount(
            own_b[routed], minlength=S).astype(np.int64)
        self.shard_hits += np.bincount(
            own_b[routed & out[:B]], minlength=S).astype(np.int64)
        if _TRACER.enabled:
            dt_us = (time.perf_counter() - t0) * 1e6
            REGISTRY.histogram("cluster.batch_us").record(dt_us)
            REGISTRY.gauge("cluster.n_compiles").set(self.n_compiles)
            for s in np.nonzero(np.bincount(own_b[routed],
                                            minlength=S))[0]:
                REGISTRY.counter(f"cluster.shard{s}.queries").inc(
                    int((own_b == s).sum()))
                REGISTRY.counter(f"cluster.shard{s}.hits").inc(
                    int((routed & out[:B] & (own_b == s)).sum()))
        return out[:B]

    def query_batch(self, us: np.ndarray, rects: np.ndarray) -> np.ndarray:
        """Batched RangeReach, bit-identical to the host path — one
        fused collective dispatch per batch (per capacity bucket)."""
        us = np.asarray(us, dtype=np.int64)
        B = len(us)
        if B == 0:
            return np.zeros(0, dtype=bool)
        fault_point("cluster.query_batch", n=B)
        t0 = time.perf_counter()
        with span("cluster.query_batch", cat="cluster", n=B):
            with span("cluster.pad_batch", cat="cluster"):
                Bb, us_dev, rsoa_dev = self._padder.pad(us, rects)
            with span("cluster.fused", cat="cluster", batch=B):
                while True:
                    kcap = min(self._kb_hwm, self.n_tiles)
                    forced, own, hit, cnt, mx = self._fused_prog(kcap)(
                        self._side, self._grid, self._qfine, self._qcoarse,
                        self._entries, *self._routing, us_dev, rsoa_dev)
                    # int(mx) blocks on the whole collective launch
                    mxi = int(mx)
                    if mxi <= kcap or kcap >= self.n_tiles:
                        break
                    self._kb_hwm = min(_bucket(mxi, 1), self.n_tiles)
                    self.stats["fused_reruns"] += 1
            return self._finish_batch(B, Bb, kcap, forced, own, hit,
                                      cnt, t0)

    def query_batch_two_phase(self, us: np.ndarray,
                              rects: np.ndarray) -> np.ndarray:
        """The retained two-dispatch reference path (sharded prune →
        host capacity bucket → sharded scan + psum) — the fused
        collective program's oracle."""
        us = np.asarray(us, dtype=np.int64)
        B = len(us)
        if B == 0:
            return np.zeros(0, dtype=bool)
        fault_point("cluster.query_batch", n=B)
        t0 = time.perf_counter()
        with span("cluster.query_batch", cat="cluster", n=B):
            with span("cluster.pad_batch", cat="cluster"):
                Bb, us_dev, rsoa_dev = self._padder.pad(us, rects)

            with span("cluster.route_prune", cat="cluster"):
                forced, own, qs, qe, cand, cnt, mx = self._prepare(
                    self._side, self._fine, self._coarse, *self._routing,
                    us_dev, rsoa_dev)
                # int(mx) blocks on the sharded prune + pmax round
                self._kb_hwm = max(
                    self._kb_hwm,
                    min(_bucket(max(int(mx), 1), 1), self.n_tiles))
            kb = self._kb_hwm
            with span("cluster.scan", cat="cluster"):
                hit = self._scan(
                    self._entries, cand[:, :, :kb], qs, qe, rsoa_dev
                )
            return self._finish_batch(B, Bb, kb, forced, own, hit,
                                      cnt, t0)

    def query(self, u: int, rect) -> bool:
        return bool(self.query_batch(np.array([u]), np.array([rect]))[0])


def sharded_engine_for(index, n_shards: Optional[int] = None,
                       interpret: Optional[bool] = None) -> ShardedEngine:
    """Memoised ``ShardedEngine`` for a built 2DReach index.

    One engine is cached per index instance: an explicit ``n_shards`` or
    ``interpret`` that disagrees with the cached engine rebuilds and
    *replaces* it (two shard layouts of the same index are never
    resident at once), while ``n_shards=None`` accepts whatever layout
    is cached — callers that need a specific count must say so.  Unlike
    ``engine_for`` there is no silent fallback: cluster serving is an
    explicit opt-in, so an unsupported index type raises a
    ``ValueError`` naming it."""
    if not isinstance(index, TwoDReachIndex):
        raise ValueError(_unsupported_msg(index, "cluster ShardedEngine"))
    eng = getattr(index, "_cluster_engine", None)
    if eng is None or (
        n_shards is not None and eng.n_shards != int(n_shards)
    ) or (
        interpret is not None and eng._interpret != bool(interpret)
    ):
        eng = ShardedEngine(index, n_shards=n_shards, interpret=interpret)
        index._cluster_engine = eng
    return eng
