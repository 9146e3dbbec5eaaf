"""What the Yelp pointer cell adds, on the CPU: the reader of the
engine's tree counter over a real engine's window on a many-component
graph and on an engine that does not count trees, and the roofline's
byte count on the pointer variant against brute force."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from bench import lbsn, roofline, spec, traffic  # noqa: E402

from test_bench_engine_counters import engine_run  # noqa: E402
from test_bench_harness import cut, small_cfg  # noqa: E402

YELP_CELL = "yelp-pointer.closed-lowdeg"


def pointer_index(cfg, seed):
    from repro.core import build_index
    from repro.core.graph import make_graph

    g = lbsn.generate(cfg, seed)
    return g, build_index(
        make_graph(g.n_nodes, g.edges, g.coords, g.spatial_mask),
        "2dreach-pointer")


def test_trees_per_batch_over_a_window():
    from repro.cluster import ShardedEngine

    cell = spec.cell(YELP_CELL)
    g, index = pointer_index(cut(cell["config"], 20000), 2**31 + 9)
    deg, ext = lbsn.out_degree(g), lbsn.spatial_extent(g)
    rng = np.random.default_rng(3)
    eng = ShardedEngine(index, n_shards=1)
    eng.query_batch(*traffic.queries(cell["traffic"], deg, ext, 64, rng))
    before = dict(eng.stats)
    trees = []
    for _ in range(4):
        us, rects = traffic.queries(cell["traffic"], deg, ext, 256, rng)
        eng.query_batch(us, rects)
        t = index.lookup_tree(us)
        trees.append(len(np.unique(t[(t >= 0) & ~index.excluded[us]])))
    window = {k: eng.stats[k] - before[k] for k in before}
    read = spec.reader("engine.trees_per_batch.pointer")
    assert read(engine_run(window)) == pytest.approx(np.mean(trees))
    assert min(trees) > 20          # the batches spread over many trees


def test_trees_per_batch_finds_nothing_without_the_counter():
    """An engine without ``trees_routed``, or a window with no batch,
    gives no reading, and raises nothing."""
    read = spec.reader("engine.trees_per_batch.pointer")
    older = {"batches": 12, "queries": 40, "host_fetches": 12}
    assert read(engine_run(older)) is None
    assert read(engine_run({})) is None
    assert read(engine_run({"batches": 0, "trees_routed": 0})) is None
    assert read(engine_run({"batches": 4, "trees_routed": 10})) == \
        pytest.approx(2.5)


SHAPES = {"gowalla": lambda: small_cfg(4000),
          "yelp": lambda: cut(spec.cell(YELP_CELL)["config"], 4000)}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_roofline_bytes_equal_brute_force_on_pointer(shape):
    """``roofline.TileIndex.call_bytes`` on the pointer variant's arena,
    against every leaf tile checked one by one."""
    g, index = pointer_index(SHAPES[shape](), 5)
    f = index.forest
    t = np.asarray(index.lookup_tree(np.arange(index.n)), np.int64)
    slices = np.zeros((index.n, 2), np.int64)
    ok = t >= 0
    slices[ok, 0], slices[ok, 1] = f.entry_off[t[ok]], f.entry_off[t[ok] + 1]
    tiles = roofline.TileIndex(f.entries, slices)
    rng = np.random.default_rng(2)
    us = rng.integers(0, g.n_nodes, 64)
    rects = traffic.region_for_extent(lbsn.spatial_extent(g), 0.05, 64, rng)
    need = set()
    for u, r in zip(us, rects):
        a, b = slices[u]
        for k in range(a // roofline.TP, -(-b // roofline.TP)):
            e = f.entries[k * roofline.TP:(k + 1) * roofline.TP]
            if (b > a and e[:, 0].min() <= r[2] and e[:, 2].max() >= r[0]
                    and e[:, 1].min() <= r[3] and e[:, 3].max() >= r[1]):
                need.add(k)
    nt = -(-len(f.entries) // roofline.TP)
    want = (28 * len(us) + 8 * nt + 16 * -(-nt // 8)
            + roofline.TILE_BYTES * len(need))
    assert tiles.call_bytes(us, rects) == want
    assert need
    if shape == "yelp":
        assert len(np.unique(t[us][t[us] >= 0])) > 20   # many trees
