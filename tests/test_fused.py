"""Fused serving megakernel — soundness sweep.

Bit-identity of the single-launch fused path against the ``query_host``
oracle AND the retained two-phase path for every 2DReach variant ×
boolean/count/collect epilogue, pow2 bucket boundaries, empty-tree /
excluded edge cases, the quantization outward-rounding property (venues
exactly on tile MBR edges), megakernel-vs-XLA-impl bit-identity, and
the zero-steady-state-recompile contract of the fused trace.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import QueryEngine, build_2dreach
from repro.core.graph import make_graph
from repro.data import get_dataset, workload
from repro.kernels.range_query import fused as F
from repro.kernels.range_query.analytics import ID_SENTINEL
from repro.kernels.range_query.descent import (
    build_tile_pyramid,
    prune_tiles_ref,
)
from repro.kernels.range_query.kernel import TB, TP
from repro.queries import range_collect_host, range_count_host


@pytest.fixture(scope="module")
def graph():
    return get_dataset("yelp", scale=0.05)


@pytest.fixture(scope="module")
def indexes(graph):
    return {v: build_2dreach(graph, variant=v)
            for v in ("base", "comp", "pointer")}


def _check_modes(idx, eng, us, rects, k=7):
    """Fused reach/count/collect vs host oracle and two-phase path."""
    want = idx.query_batch(us, rects)
    got = eng.query_batch(us, rects)
    assert (want == got).all()
    assert (eng.query_batch_two_phase(us, rects) == want).all()

    wc = np.asarray(range_count_host(idx, us, rects))
    assert (eng.count_batch(us, rects) == wc).all()
    assert (eng.count_batch_two_phase(us, rects) == wc).all()

    wcol = range_collect_host(idx, us, rects, k)
    gcol = eng.collect_batch(us, rects, k)
    tcol = eng.collect_batch_two_phase(us, rects, k)
    for other in (gcol, tcol):
        assert (wcol.ids == other.ids).all()
        assert (wcol.counts == other.counts).all()
        assert (wcol.overflow == other.overflow).all()


# ---------------------------------------------------------------- identity
@pytest.mark.parametrize("variant", ["base", "comp", "pointer"])
def test_fused_identity_all_variants(graph, indexes, variant):
    idx = indexes[variant]
    eng = QueryEngine(idx)
    assert eng.path == "fused"
    for seed in range(3):
        us, rects = workload(graph, 150, extent_ratio=0.06, seed=seed)
        _check_modes(idx, eng, us, rects)


@pytest.mark.parametrize("B", [1, TB, TB + 1])
def test_fused_bucket_boundaries(graph, indexes, B):
    idx = indexes["comp"]
    eng = QueryEngine(idx)
    us, rects = workload(graph, B, extent_ratio=0.05, seed=B)
    _check_modes(idx, eng, us, rects)


def test_fused_empty_tree_and_excluded_edge_cases():
    """tid==-1 vertices, empty forests, spatial-sink (excluded) query
    vertices — the fused trace must answer exactly like host."""
    edges = np.array([[0, 1]], dtype=np.int64)
    coords = np.array([[0, 0], [1, 1], [0, 0], [5, 5]], dtype=np.float32)
    spatial = np.array([False, True, False, True])
    g = make_graph(4, edges, coords, spatial)
    for variant in ("base", "comp", "pointer"):
        idx = build_2dreach(g, variant=variant)
        eng = QueryEngine(idx)
        us = np.array([0, 2, 3, 1])
        rects = np.array([[0.5, 0.5, 1.5, 1.5]] * 4, dtype=np.float32)
        _check_modes(idx, eng, us, rects, k=2)
        # excluded vertex answers by its own point (Alg. 2)
        own = np.array([[4.5, 4.5, 5.5, 5.5]] * 4, dtype=np.float32)
        assert (eng.query_batch(us, own)
                == idx.query_batch(us, own)).all()


def test_fused_megakernel_matches_xla_impl(indexes, graph):
    """The Pallas megakernel (interpret) and the fused XLA program are
    the same function bit-for-bit, through the engine surface."""
    idx = indexes["comp"]
    ex = QueryEngine(idx, fused_impl="xla")
    ep = QueryEngine(idx, fused_impl="pallas")
    us, rects = workload(graph, 2 * TB, extent_ratio=0.06, seed=4)
    assert (ex.query_batch(us, rects) == ep.query_batch(us, rects)).all()
    assert (ex.count_batch(us, rects) == ep.count_batch(us, rects)).all()
    cx = ex.collect_batch(us, rects, 5)
    cp = ep.collect_batch(us, rects, 5)
    assert (cx.ids == cp.ids).all() and (cx.counts == cp.counts).all()


# Leaf-tile runs [a, b] that each query of a query tile activates, for an
# arena of exactly 18 chunks of 128 tiles (so lane ntp - 1 is a real
# tile); a query tile lists up to TB runs, the rest of its queries miss
_LOOKUP_NT = 18 * 128
_LOOKUP_CASES = {
    "ends": [[(0, 0), (_LOOKUP_NT - 1, _LOOKUP_NT - 1)], [(1, 1)]],
    "chunk-crossing": [[(120, 135), (250, 260), (1020, 1030)],
                       [(127, 128), (1151, 1153)]],
    "full-chunk": [[(384, 511)], [(383, 512)]],
    "far-apart": [[(5, 5), (2200, 2200)], [(130, 130), (1700, 1700)]],
    "none": [[], []],
    "kcap-below-count": [[(0, 40), (700, 740)], [(2000, 2100)]],
}
_LOOKUP_KCAP = {"kcap-below-count": 64}


def _wide_arena_slices():
    """An arena of 2,348 leaf tiles, random rects, and query slices that
    span the arena, cross a thousand-tile boundary, end in the last tile
    or are empty."""
    rng = np.random.default_rng(7)
    nt = 2348
    P, c = nt * TP, 1024 * TP
    pts = rng.uniform(0, 100, (P, 2)).astype(np.float32)
    pts = pts[np.argsort(pts[:, 0], kind="stable")]   # tiles in x strips
    esoa = np.concatenate([pts.T, pts.T])
    B = 2 * TB
    slices = np.array([[0, P], [c - 5 * TP, c + 9 * TP + 3],
                       [2 * c + 7, P - 11], [0, 0], [c, 2 * c],
                       [5, c - 1], [P - TP, P], [2 * c - TP, 2 * c + TP]]
                      * (B // 8), np.int32)
    lo = rng.uniform(0, 95, (B, 2))
    rsoa = np.concatenate([lo, lo + rng.uniform(0.2, 5, (B, 2))],
                          axis=1).T.astype(np.float32)
    return esoa, np.array([0, 0, 100, 100], np.float64), rsoa, slices, None


def _wide_arena_runs(case):
    """Tile t holds points with x in [t + 0.2, t + 0.8], so a rect over
    x in [a + 0.1, b + 0.9] meets points of tiles a..b and activates no
    other, by more than the quantization's slack; a query with no run
    looks left of the arena."""
    rng = np.random.default_rng(11)
    nt = _LOOKUP_NT
    P = nt * TP
    x = (np.arange(P) // TP + rng.uniform(0.2, 0.8, P)).astype(np.float32)
    y = rng.uniform(0, 100, P).astype(np.float32)
    esoa = np.stack([x, y, x, y])
    tiles = _LOOKUP_CASES[case]
    rsoa = np.tile(np.array([[-50.0], [0.0], [-40.0], [100.0]], np.float32),
                   (1, TB * len(tiles)))
    want = []
    for i, runs in enumerate(tiles):
        for j, (a, b) in enumerate(runs):
            rsoa[[0, 2], i * TB + j] = a + 0.1, b + 0.9
        want.append(len(set().union(*(range(a, b + 1) for a, b in runs))))
    slices = np.tile([0, P], (rsoa.shape[1], 1)).astype(np.int32)
    return (esoa, np.array([0, 0, nt, 100], np.float64), rsoa, slices,
            np.array(want, np.int32))


_WIDE_CASES = (
    [pytest.param(m, k, None, id=f"{m}-{k}") for m, k in
     [("reach", 64), ("count", 64), ("collect", 64), ("reach", 8)]]
    + [pytest.param(m, _LOOKUP_KCAP.get(case, 256), case,
                    id=f"{case}-{m}")
       for case in _LOOKUP_CASES for m in ("reach", "count", "collect")])


@pytest.mark.parametrize("mode,kcap,case", _WIDE_CASES)
def test_megakernel_matches_xla_on_a_wide_arena(mode, kcap, case):
    """The megakernel (interpret) and the fused XLA program agree bit for
    bit on the answers, the candidate counts and, in collect mode, the
    worklist order.  On 2,348 leaf tiles with random rects and query
    slices, and a capacity below some counts (the truncated-scan
    contract); and on 18 chunks of 128 tiles with the active tiles
    placed on the edges of the walk's next-active lookup: the first and
    the last lane, runs across a chunk boundary, one full chunk, actives
    many chunks apart, none at all, and more actives than the capacity."""
    esoa, extent, rsoa, slices, want = (
        _wide_arena_slices() if case is None
        else _wide_arena_runs(case))
    fine, coarse, nt = build_tile_pyramid(esoa, dim=2)
    assert nt == esoa.shape[1] // TP
    grid = F.make_quant_grid(extent, 2)
    r16, r32 = F.quantize_rects(grid, jnp.asarray(rsoa), 2)
    args = (F.quantize_fine(grid, jnp.asarray(fine), 2),
            F.quantize_coarse(grid, jnp.asarray(coarse), 2),
            jnp.asarray(esoa),
            jnp.arange(esoa.shape[1], dtype=jnp.int32)[None],
            r16, r32, jnp.asarray(rsoa), jnp.asarray(slices[:, 0]),
            jnp.asarray(slices[:, 1]))
    ox, cx = F.fused_serve_xla(*args, mode=mode, kcap=kcap, nt=nt)
    op, cp = F.fused_serve_pallas(*args, mode=mode, kcap=kcap, nt=nt,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(cp), np.asarray(cx))
    np.testing.assert_array_equal(np.asarray(op), np.asarray(ox))
    if case is None:
        assert (np.asarray(cx) > kcap).any() and np.asarray(ox).any()
    else:
        np.testing.assert_array_equal(np.asarray(cx), want)
        assert (want > kcap).any() == (case == "kcap-below-count")
        empty = 0 if mode != "collect" else ID_SENTINEL
        assert (np.asarray(ox) != empty).any() == (case != "none")


# ------------------------------------------------------------ quantization
def _edge_arena(rng, P):
    """Entry arena whose venue boxes end exactly on tile-MBR edges."""
    pts = np.round(rng.uniform(0, 100, (P, 2)) * 4) / 4  # lattice coords
    pts = pts.astype(np.float32)
    Pp = max(TP, -(-P // TP) * TP)
    esoa = np.empty((4, Pp), np.float32)
    esoa[:2] = 1.0
    esoa[2:] = 0.0
    esoa[:2, :P] = pts.T
    esoa[2:, :P] = pts.T                      # degenerate boxes = points
    return esoa, pts


def test_quantized_prune_superset_of_f32_on_mbr_edges():
    """Outward-rounding property: the quantized prune mask contains the
    f32 prune mask even when rect edges coincide exactly with venue
    coords / tile MBR edges (the worst case for any rounding)."""
    rng = np.random.default_rng(0)
    esoa, pts = _edge_arena(rng, 5 * TP + 3)
    fine, coarse, nt = build_tile_pyramid(esoa, dim=2)
    extent = np.concatenate([esoa[:2, : len(pts)].min(1),
                             esoa[2:, : len(pts)].max(1)])
    grid = F.make_quant_grid(extent.astype(np.float64), 2)
    qf = F.quantize_fine(grid, jnp.asarray(fine), 2)
    qc = F.quantize_coarse(grid, jnp.asarray(coarse), 2)

    B = 4 * TB
    # rects whose edges ARE tile MBR corners / venue coords exactly
    lo = pts[rng.integers(0, len(pts), B)]
    hi = np.maximum(lo, pts[rng.integers(0, len(pts), B)])
    rsoa = np.concatenate([lo, hi], axis=1).T.astype(np.float32)
    qs = np.zeros(B, np.int32)
    qe = np.full(B, len(pts), np.int32)
    r16, r32 = F.quantize_rects(grid, jnp.asarray(rsoa), 2)

    qmask = np.asarray(F.quantized_prune_mask(
        qf, qc, r16, r32, jnp.asarray(qs), jnp.asarray(qe)))
    fmask = np.asarray(
        prune_tiles_ref(fine, coarse, rsoa, jnp.asarray(qs),
                        jnp.asarray(qe))).astype(bool)
    assert (qmask[:, : fmask.shape[1]] | ~fmask).all(), \
        "quantized prune dropped a tile the f32 prune keeps (unsound)"


def test_fused_exact_on_rect_edges():
    """End-to-end: rect edges exactly on venue coordinates — the exact
    f32 leaf predicate must decide, not the quantized prune."""
    rng = np.random.default_rng(3)
    n, nv = 80, 40
    coords = (np.round(rng.uniform(0, 50, (n, 2)) * 2) / 2).astype(np.float32)
    spatial = np.zeros(n, bool)
    spatial[:nv] = True
    edges = np.stack([np.arange(nv, n), rng.integers(0, nv, n - nv)], 1)
    g = make_graph(n, edges.astype(np.int64), coords, spatial)
    idx = build_2dreach(g, variant="comp")
    eng = QueryEngine(idx)
    us = rng.integers(nv, n, 3 * TB)
    # rect corners sit exactly on venue points: closed-interval hits
    c = coords[rng.integers(0, nv, 3 * TB)]
    rects = np.concatenate([c, c], axis=1)     # zero-area rects on venues
    assert (eng.query_batch(us, rects) == idx.query_batch(us, rects)).all()
    wc = np.asarray(range_count_host(idx, us, rects))
    assert (eng.count_batch(us, rects) == wc).all()


# ------------------------------------------------------------ compile-once
def test_fused_zero_steady_state_recompiles(graph, indexes):
    eng = QueryEngine(indexes["pointer"])
    shapes = [(0, 1), (1, TB), (2, 100), (3, 128), (4, 3)]
    # warmup pass: traces per (mode, bucket) plus possible capacity
    # ratchet reruns (monotone hwm — each bumps at most one new kcap)
    for seed, B in shapes:
        us, rects = workload(graph, B, extent_ratio=0.05, seed=seed)
        eng.query_batch(us, rects)
        eng.count_batch(us, rects)
        eng.collect_batch(us, rects, 6)
    warm = eng.n_compiles
    # steady state: previously-seen shapes and workloads, any order —
    # zero retraces and zero capacity reruns
    reruns = eng.stats["fused_reruns"]
    for seed, B in reversed(shapes):
        us, rects = workload(graph, B, extent_ratio=0.05, seed=seed)
        eng.query_batch(us, rects)
        eng.count_batch(us, rects)
        eng.collect_batch(us, rects, 6)
    assert eng.n_compiles == warm, \
        "fused steady-state serving must not retrace"
    assert eng.stats["fused_reruns"] == reruns, \
        "capacity hwm must not rerun on a previously-seen workload"


def test_resilient_two_phase_degradation(graph, indexes):
    """degraded_path='two_phase': a tripped breaker reroutes to the
    retained two-phase device path, still bit-identical."""
    from repro.resilience import ResilientEngine
    from repro.resilience.breaker import BreakerPolicy

    idx = indexes["comp"]
    eng = QueryEngine(idx)
    # reset_timeout_s large so the breaker stays open across both calls
    # (the default 1s would half-open while the first fallback compiles)
    res = ResilientEngine(eng, idx, degraded_path="two_phase",
                          breaker=BreakerPolicy(reset_timeout_s=3600.0))
    res.trip()
    us, rects = workload(graph, 50, extent_ratio=0.05, seed=8)
    assert (res.query_batch(us, rects) == idx.query_batch(us, rects)).all()
    assert res.last_report["degraded"].all()
    wc = np.asarray(range_count_host(idx, us, rects))
    assert (res.count_batch(us, rects) == wc).all()
    assert res.stats["fallback_batches"] >= 2
