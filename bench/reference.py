"""Plain RangeReach reference, computed from the generated graph alone.

RangeReach(u, R) is true when some venue reachable from ``u`` (``u``
itself included) lies in the closed rectangle ``R``.  All vertices of
one strongly connected component reach the same set, so the reference
finds the components (scipy), takes one breadth-first search per
distinct component among the queried vertices, and answers each query
against that component's reachable venues.  It imports nothing of the
program and takes nothing the program made.

A component costs what it reaches, not the size of the graph: the
search expands its frontier over the adjacency's own ``indptr`` and
``indices``, and resets only the visited marks it set; a component's
venues are compared with its rectangles pair by pair where that is
cheaper than the grid of ``any_point_in_rects``.

The control (``precision="bfloat16"``) is the same reference with venue
and rectangle coordinates rounded to bfloat16, the nearest precision
below the float32 the configuration states: a leaf test at a lower
precision, the step that would tempt a change to the kernel.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import connected_components

GRID = 1024
# venues x rectangles up to which every pair is compared: about 5 ns a
# pair up to 1M pairs, against 27-37 ms for the grid at any size (on an
# Intel Xeon host CPU); past 5M pairs the pairs cost more than the grid
DIRECT_PAIRS = 1 << 22
# a frontier whose out-edges pass this share of all edges is expanded by
# one pass over every edge: about 4 ns an edge, against about 40 ns a
# gathered edge (same CPU)
DENSE_SHARE = 8


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float32":
        return np.asarray(x, np.float32)
    if precision == "bfloat16":
        import ml_dtypes

        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
            np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def any_point_in_rects(pts: np.ndarray, rects: np.ndarray) -> np.ndarray:
    """(q,) bool: does any point of ``pts`` (m, 2) lie in each rect
    [xmin, ymin, xmax, ymax] of ``rects`` (q, 4), bounds included.

    Exact: a grid over the points answers the rects it can decide (a
    point in a cell strictly inside a rect's cell range lies inside it,
    since the cell map is monotone; an empty cell range holds none), and
    the rest are tested point by point."""
    q = len(rects)
    if len(pts) == 0 or q == 0:
        return np.zeros(q, dtype=bool)
    pts = np.asarray(pts, np.float32)
    rects = np.asarray(rects, np.float32)
    lo = pts.min(axis=0).astype(np.float64)
    width = np.maximum(pts.max(axis=0).astype(np.float64) - lo, 1e-30)

    def cell(v, a):
        c = np.floor((v.astype(np.float64) - lo[a]) / width[a] * GRID)
        return np.clip(c, 0, GRID - 1).astype(np.int64)

    counts = np.bincount(cell(pts[:, 0], 0) * GRID + cell(pts[:, 1], 1),
                         minlength=GRID * GRID).reshape(GRID, GRID)
    pre = np.zeros((GRID + 1, GRID + 1), dtype=np.int64)
    pre[1:, 1:] = counts.cumsum(0).cumsum(1)

    def box(x0, x1, y0, y1):
        """Points in cells [x0, x1] x [y0, y1] (0 where empty)."""
        ok = (x0 <= x1) & (y0 <= y1)
        x0, y0 = np.minimum(x0, x1 + 1), np.minimum(y0, y1 + 1)
        s = (pre[x1 + 1, y1 + 1] - pre[x0, y1 + 1] - pre[x1 + 1, y0]
             + pre[x0, y0])
        return np.where(ok, s, 0)

    cx0, cx1 = cell(rects[:, 0], 0), cell(rects[:, 2], 0)
    cy0, cy1 = cell(rects[:, 1], 1), cell(rects[:, 3], 1)
    proper = (rects[:, 0] <= rects[:, 2]) & (rects[:, 1] <= rects[:, 3])
    inner = box(cx0 + 1, cx1 - 1, cy0 + 1, cy1 - 1)
    outer = box(cx0, cx1, cy0, cy1)
    ans = proper & (inner > 0)
    order = np.argsort(pts[:, 0], kind="stable")
    xs, ys = pts[order, 0], pts[order, 1]
    for i in np.nonzero(proper & (inner == 0) & (outer > 0))[0]:
        a = np.searchsorted(xs, rects[i, 0], side="left")
        b = np.searchsorted(xs, rects[i, 2], side="right")
        y = ys[a:b]
        ans[i] = bool(np.any((y >= rects[i, 1]) & (y <= rects[i, 3])))
    return ans


def any_point_in_rects_pairwise(pts: np.ndarray,
                                rects: np.ndarray) -> np.ndarray:
    """``any_point_in_rects`` by comparing every point with every rect."""
    pts = np.asarray(pts, np.float32)
    rects = np.asarray(rects, np.float32)
    x, y = pts[:, 0], pts[:, 1]
    return ((x >= rects[:, 0:1]) & (x <= rects[:, 2:3])
            & (y >= rects[:, 1:2]) & (y <= rects[:, 3:4])).any(axis=1)


class Reference:
    """The graph's components, ready to answer queries.  Build once per
    run, after the window."""

    def __init__(self, n: int, edges: np.ndarray, coords: np.ndarray,
                 spatial_mask: np.ndarray):
        e = np.asarray(edges, np.int64)
        self.adj = csr_matrix(
            (np.ones(len(e), bool), (e[:, 0], e[:, 1])), shape=(n, n))
        _, self.comp = connected_components(
            self.adj, directed=True, connection="strong")
        self.indptr, self.indices = self.adj.indptr, self.adj.indices
        self.coords = coords
        self.spatial_mask = spatial_mask
        self.seen = np.zeros(n, dtype=bool)   # all False between searches
        self.slot = np.zeros(n, dtype=np.int64)

    def reach(self, u: int) -> np.ndarray:
        """Every vertex reachable from ``u``, ``u`` included, each once.

        One frontier at a time: a small one gathers its out-edges from
        ``indptr`` and ``indices``; one whose out-edges pass a
        ``1 / DENSE_SHARE`` share of all edges takes a single pass over
        every edge (a product of the adjacency with its mask)."""
        indptr, indices, seen = self.indptr, self.indices, self.seen
        front = np.array([u], dtype=np.int64)
        seen[u] = True
        found = [front]
        while len(front):
            lo, hi = indptr[front], indptr[front + 1]
            cnt = hi - lo
            edges = int(cnt.sum())
            if DENSE_SHARE * edges > len(indices):
                mask = np.zeros(len(seen), dtype=bool)
                mask[front] = True
                front = np.flatnonzero((self.adj.T @ mask) & ~seen)
                seen[front] = True
            else:
                at = np.arange(edges) + np.repeat(lo - np.cumsum(cnt) + cnt,
                                                  cnt)
                nb = indices[at]
                nb = nb[~seen[nb]]
                seen[nb] = True
                # one entry per vertex: the position whose write survived
                k = np.arange(len(nb))
                self.slot[nb] = k
                front = nb[self.slot[nb] == k]
            found.append(front)
        out = np.concatenate(found)
        seen[out] = False
        return out

    def answers(self, us: np.ndarray, rects: np.ndarray,
                precision: str = "float32") -> np.ndarray:
        """(q,) bool answers for queries (us, rects)."""
        us = np.asarray(us, np.int64)
        rects = _round(rects, precision)
        out = np.zeros(len(us), dtype=bool)
        if len(us) == 0:
            return out
        qc = self.comp[us]
        order = np.argsort(qc, kind="stable")
        for sel in np.split(order, np.flatnonzero(np.diff(qc[order])) + 1):
            seen = self.reach(int(us[sel[0]]))
            venues = seen[self.spatial_mask[seen]]
            test = (any_point_in_rects_pairwise
                    if len(venues) * len(sel) <= DIRECT_PAIRS
                    else any_point_in_rects)
            out[sel] = test(_round(self.coords[venues], precision),
                            rects[sel])
        return out


def compare(served: np.ndarray, want: np.ndarray) -> Tuple[int, int]:
    """(mismatches, compared) of served answers against the reference."""
    served = np.asarray(served, dtype=bool)
    return int(np.sum(served != want)), len(want)
