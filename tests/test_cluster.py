"""Cluster serving: partitioning, the sharded engine's bit-identity to
the host oracle across shard counts, the micro-batching frontend, and
the sharded base probe under a DynamicIndex overlay.

Runs on however many devices the host exposes: shards stack per device,
so the 8-shard layout is exercised even single-device (CI additionally
runs this file under XLA_FLAGS=--xla_force_host_platform_device_count=8
for a real 1-shard-per-device mesh)."""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from repro.cluster import (
    Frontend,
    ShardedEngine,
    balanced_assignment,
    partition_forest,
    sharded_engine_for,
)
from repro.core import batch_query, build_2dreach, build_index
from repro.core.graph import make_graph
from repro.data import get_dataset, workload
from repro.kernels.range_query.kernel import TB

SHARD_COUNTS = (1, 2, 8)


@pytest.fixture(scope="module")
def graph():
    return get_dataset("yelp", scale=0.05)


@pytest.fixture(scope="module")
def indexes(graph):
    return {v: build_2dreach(graph, variant=v)
            for v in ("base", "comp", "pointer")}


# ---------------------------------------------------------------- partition
def test_balanced_assignment_lpt():
    w = np.array([10, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1], dtype=np.int64)
    a = balanced_assignment(w, 2)
    loads = np.bincount(a, weights=w, minlength=2)
    # LPT: the heavy item alone on one shard, the ten units on the other
    assert sorted(loads.tolist()) == [10.0, 10.0]
    # deterministic
    assert (a == balanced_assignment(w, 2)).all()


def test_partition_routing_arrays(indexes):
    forest = indexes["comp"].forest
    for S in SHARD_COUNTS:
        part = partition_forest(forest, S)
        counts = np.diff(forest.entry_off)
        assert part.n_trees == forest.n_trees
        seen = np.zeros(forest.n_trees, dtype=bool)
        for s, trees in enumerate(part.shard_trees):
            lo = 0
            for t in trees:
                assert part.tree_shard[t] == s
                assert part.tree_qs[t] == lo
                assert part.tree_qe[t] == lo + counts[t]
                lo += counts[t]
                seen[t] = True
            assert part.shard_entries[s] == lo
        assert seen.all(), "every tree must land on exactly one shard"
        assert part.shard_entries.sum() == counts.sum()


def test_partition_balance(indexes):
    forest = indexes["comp"].forest
    counts = np.diff(forest.entry_off).astype(np.int64)
    for S in (2, 4):
        part = partition_forest(forest, S)
        # LPT bound: max load <= perfect + the heaviest item
        perfect = counts.sum() / S
        assert part.shard_entries.max() <= perfect + counts.max()


def test_partition_rejects_bad_shards(indexes):
    with pytest.raises(ValueError):
        partition_forest(indexes["comp"].forest, 0)


# ---------------------------------------------------------------- exactness
@pytest.mark.parametrize("variant", ["base", "comp", "pointer"])
@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_sharded_matches_host_oracle(graph, indexes, variant, n_shards):
    """The acceptance gate: bit-identical to query_host on every 2DReach
    variant for shard counts {1, 2, 8}."""
    idx = indexes[variant]
    eng = ShardedEngine(idx, n_shards=n_shards)
    for seed in range(3):
        us, rects = workload(graph, 160, extent_ratio=0.05, seed=seed)
        want = idx.query_batch(us, rects)   # host path == query_host oracle
        got = eng.query_batch(us, rects)
        assert (want == got).all()
        assert got.dtype == np.bool_ and got.shape == want.shape
    # every probed query was routed to exactly one shard
    assert eng.shard_queries.sum() <= eng.stats["queries"]


def test_sharded_trees_empty_on_some_shards():
    """More shards than trees: shards with an empty arena must stay
    inert, and answers stay exact."""
    # graph: 0 -> 1 (venue), 2 isolated user, 3 isolated venue
    edges = np.array([[0, 1]], dtype=np.int64)
    coords = np.array([[0, 0], [1, 1], [0, 0], [5, 5]], dtype=np.float32)
    spatial = np.array([False, True, False, True])
    g = make_graph(4, edges, coords, spatial)
    for variant in ("base", "comp", "pointer"):
        idx = build_2dreach(g, variant=variant)
        assert idx.forest.n_trees < 8
        eng = ShardedEngine(idx, n_shards=8)
        us = np.array([0, 2, 3, 1])
        rects = np.array([[0.5, 0.5, 1.5, 1.5]] * 4, dtype=np.float32)
        want = idx.query_batch(us, rects)
        got = eng.query_batch(us, rects)
        assert (want == got).all(), variant
        assert want[0] and not want[1]


def test_sharded_empty_forest():
    """A graph with no reachable venues at all: T=0 trees, every shard
    arena empty, every answer False (or the Alg. 2 point test)."""
    edges = np.array([[0, 1]], dtype=np.int64)
    coords = np.zeros((2, 2), dtype=np.float32)
    g = make_graph(2, edges, coords, np.zeros(2, dtype=bool))
    idx = build_2dreach(g, variant="comp")
    assert idx.forest.n_trees == 0
    eng = ShardedEngine(idx, n_shards=2)
    us = np.array([0, 1])
    rects = np.array([[-1, -1, 1, 1]] * 2, dtype=np.float32)
    assert (eng.query_batch(us, rects) == idx.query_batch(us, rects)).all()


@pytest.mark.parametrize("variant", ["comp", "pointer"])
def test_sharded_spatial_query_vertices(graph, indexes, variant):
    """Alg. 2 special case: excluded (spatial-sink) query vertices answer
    by their own point — fused identically on every device."""
    idx = indexes[variant]
    eng = ShardedEngine(idx, n_shards=2)
    exc = np.nonzero(idx.excluded)[0]
    rng = np.random.default_rng(7)
    us = rng.choice(exc, size=32)
    pts = idx.coords[us]
    rects = np.concatenate([pts - 0.01, pts + 0.01], axis=1).astype(np.float32)
    rects[::2] += 1e3    # guaranteed miss
    want = idx.query_batch(us, rects)
    got = eng.query_batch(us, rects)
    assert (want == got).all()
    assert want[1::2].all() and not want[::2].any()


@pytest.mark.parametrize("B", [1, TB, TB + 1, 100])
def test_sharded_bucket_boundaries(graph, indexes, B):
    idx = indexes["comp"]
    eng = ShardedEngine(idx, n_shards=2)
    us, rects = workload(graph, B, extent_ratio=0.05, seed=B)
    assert (idx.query_batch(us, rects) == eng.query_batch(us, rects)).all()


def test_sharded_empty_batch(indexes):
    eng = ShardedEngine(indexes["comp"], n_shards=2)
    out = eng.query_batch(np.zeros(0, np.int64), np.zeros((0, 4), np.float32))
    assert out.shape == (0,) and out.dtype == np.bool_


# ---------------------------------------------------------- compile-once
def test_sharded_no_steady_state_recompiles(graph, indexes):
    idx = indexes["pointer"]
    eng = ShardedEngine(idx, n_shards=8)
    for seed, B in [(0, 1), (1, 8), (2, 100), (3, 128)]:
        us, rects = workload(graph, B, extent_ratio=0.05, seed=seed)
        eng.query_batch(us, rects)
    warm = eng.n_compiles
    for seed, B in [(10, 3), (11, 100), (12, 77), (13, 128), (14, 1)]:
        us, rects = workload(graph, B, extent_ratio=0.05, seed=seed)
        assert (idx.query_batch(us, rects) == eng.query_batch(us, rects)).all()
    assert eng.n_compiles == warm
    assert eng.stats["uploads"] == 1


@pytest.mark.parametrize("n_shards", [1, 8])
def test_sharded_one_host_fetch_per_batch(n_shards):
    """The fused path copies one packed vector to the host per batch,
    and one more per capacity re-run; what it unpacks equals the
    two-phase reference's own copies: answers, candidate-tile counts and
    the per-shard counters."""
    # a chain of 20 users, each checking in at 150 of 3,000 venues: user
    # 0 reaches every venue, so its tree spans many leaf tiles
    rng = np.random.default_rng(0)
    nu, nv = 20, 3000
    edges = [(i, i + 1) for i in range(nu - 1)] + [
        (i, nu + v) for i in range(nu)
        for v in rng.choice(nv, 150, replace=False)]
    coords = np.r_[np.zeros((nu, 2)), rng.random((nv, 2))]
    g = make_graph(nu + nv, np.array(edges, np.int64),
                   coords.astype(np.float32), np.arange(nu + nv) >= nu)
    idx = build_2dreach(g, variant="base")
    fused = ShardedEngine(idx, n_shards=n_shards)
    ref = ShardedEngine(idx, n_shards=n_shards)

    def batch(seed, n, half):
        r = np.random.default_rng(seed)
        c = r.random((n, 2))
        return r.integers(0, nu, n), np.c_[c - half, c + half].astype(
            np.float32)

    narrow = [batch(s, n, 0.01) for s, n in [(1, 100), (2, 128), (3, 7),
                                             (4, 64)]]
    # the whole extent meets more candidate tiles than the narrow
    # batches ratcheted the capacity to: that batch re-runs once
    us, _ = batch(5, 64, 0.01)
    wide = (us, np.tile(np.float32([0, 0, 1, 1]), (64, 1)))
    reruns = []
    for us, rects in narrow + narrow + [wide]:
        before = fused.stats["fused_reruns"]
        got = fused.query_batch(us, rects)
        reruns.append(fused.stats["fused_reruns"] - before)
        assert (got == ref.query_batch_two_phase(us, rects)).all()
        assert (got == idx.query_batch(us, rects)).all()
        assert fused.stats["host_fetches"] == \
            fused.stats["batches"] + fused.stats["fused_reruns"]
    assert reruns[len(narrow):] == [0] * len(narrow) + [1]
    assert fused.stats["tiles_scanned"] == ref.stats["tiles_scanned"]
    assert (fused.shard_queries == ref.shard_queries).all()
    assert (fused.shard_hits == ref.shard_hits).all()
    assert fused.shard_hits.sum() > 0
    assert ref.stats["host_fetches"] == 0


def test_sharded_engine_for_memoised_and_strict(graph, indexes):
    idx = indexes["base"]
    assert sharded_engine_for(idx) is sharded_engine_for(idx)
    us = np.array([0]); rects = np.array([[0, 0, 1, 1]], np.float32)
    assert (batch_query(idx, us, rects, engine="cluster")
            == batch_query(idx, us, rects)).all()
    # n_shards change rebuilds rather than silently serving the old cut
    eng2 = sharded_engine_for(idx, n_shards=2)
    assert eng2.n_shards == 2
    # cluster serving is explicit opt-in: unsupported index types raise
    geo = build_index(graph, "georeach")
    with pytest.raises(ValueError, match="GeoReachIndex"):
        sharded_engine_for(geo)
    with pytest.raises(ValueError, match="cluster"):
        batch_query(geo, us, rects, engine="cluster")


def test_sharded_mesh_divisibility(indexes):
    import jax

    from repro.cluster import make_shard_mesh

    if len(jax.devices()) >= 2:     # exercised by the CI 8-device job
        mesh = make_shard_mesh(2)
        with pytest.raises(ValueError, match="multiple"):
            ShardedEngine(indexes["comp"], n_shards=3, mesh=mesh)
    # 3 shards with no mesh given: falls back to a divisor device count
    eng = ShardedEngine(indexes["comp"], n_shards=3)
    assert eng.n_shards == 3
    assert eng.n_shards % eng.mesh.shape["data"] == 0


# ---------------------------------------------------------------- frontend
def test_frontend_answers_match_host(graph, indexes):
    idx = indexes["comp"]
    eng = ShardedEngine(idx, n_shards=2)
    us, rects = workload(graph, 300, extent_ratio=0.05, seed=5)
    want = idx.query_batch(us, rects)
    with Frontend(eng, max_batch=64, max_delay=5e-3) as fe:
        got = fe.submit_many(us, rects, timeout=60)
        assert (got == want).all()
        assert fe.stats["n_flush_full"] >= 1
        assert fe.stats["batched_queries"] == 300


def test_frontend_deadline_flush(graph, indexes):
    """A lone request (batch never fills) must still resolve within the
    deadline, via the deadline-flush path."""
    idx = indexes["comp"]
    eng = ShardedEngine(idx, n_shards=2)
    us, rects = workload(graph, 1, extent_ratio=0.05, seed=9)
    with Frontend(eng, max_batch=64, max_delay=2e-3) as fe:
        fe.warmup(us, rects)
        t0 = time.monotonic()
        got = fe.submit(int(us[0]), rects[0]).result(timeout=10)
        dt = time.monotonic() - t0
        assert got == bool(idx.query_batch(us, rects)[0])
        assert fe.stats["n_flush_deadline"] >= 1
        assert dt < 5.0   # deadline fired, not a hang


def test_frontend_steady_state_no_recompiles(graph, indexes):
    """The acceptance gate: zero recompiles in steady state under the
    micro-batching frontend."""
    idx = indexes["comp"]
    eng = ShardedEngine(idx, n_shards=8)
    us, rects = workload(graph, 400, extent_ratio=0.05, seed=6)
    with Frontend(eng, max_batch=64, max_delay=2e-3) as fe:
        fe.warmup(us[:64], rects[:64])
        fe.submit_many(us, rects, timeout=60)   # warm the K mark
        fe.warmup(us[:64], rects[:64])   # re-pin buckets at that mark
        fe.submit_many(us, rects, timeout=60)   # structure-matched
        # shakeout: same submission pattern as the asserted pass, so any
        # regrouping-induced ratchet of the K mark lands here, not below
        warm = eng.n_compiles
        got = fe.submit_many(us, rects, timeout=60)
        assert eng.n_compiles == warm, "steady-state recompile"
    assert (got == idx.query_batch(us, rects)).all()


def test_frontend_backpressure_and_close(graph, indexes):
    idx = indexes["comp"]
    eng = ShardedEngine(idx, n_shards=2)
    us, rects = workload(graph, 64, extent_ratio=0.05, seed=4)
    fe = Frontend(eng, max_batch=8, max_delay=1e-3, max_queue=8)
    errs = []

    def feed():
        try:
            for i in range(64):
                fe.submit(int(us[i]), rects[i])
        except Exception as e:  # pragma: no cover
            errs.append(e)

    t = threading.Thread(target=feed)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive() and not errs
    assert fe.stats["max_pending_seen"] <= 8
    fe.close(timeout=30)
    with pytest.raises(RuntimeError):
        fe.submit(int(us[0]), rects[0])


def test_frontend_validates_config(indexes):
    eng = ShardedEngine(indexes["comp"], n_shards=1)
    with pytest.raises(ValueError):
        Frontend(eng, max_batch=0)
    with pytest.raises(ValueError):
        Frontend(eng, max_batch=64, max_queue=8)


def test_frontend_survives_cancelled_future(graph, indexes):
    """A client cancelling its future must not kill the scheduler or
    strand the rest of the batch."""
    idx = indexes["comp"]
    us, rects = workload(graph, 16, extent_ratio=0.05, seed=13)
    with Frontend(idx, max_batch=8, max_delay=50e-3) as fe:
        cancelled = fe.submit(int(us[0]), rects[0])
        assert cancelled.cancel()           # before any flush fires
        got = fe.submit_many(us[1:], rects[1:], timeout=30)
    assert (got == idx.query_batch(us[1:], rects[1:])).all()


def test_frontend_rejects_ragged_rects_and_survives(graph, indexes):
    """A malformed rect is rejected in the caller's thread; the
    scheduler thread keeps serving afterwards."""
    idx = indexes["comp"]
    us, rects = workload(graph, 8, extent_ratio=0.05, seed=12)
    with Frontend(idx, max_batch=4, max_delay=1e-3) as fe:
        fe.submit(int(us[0]), rects[0])
        with pytest.raises(ValueError, match="coords"):
            fe.submit(int(us[1]), rects[1][:3])     # 3 coords, not 4
        got = fe.submit_many(us, rects, timeout=30)
    assert (got == idx.query_batch(us, rects)).all()


def test_frontend_works_with_host_index(graph, indexes):
    """Engine-agnostic: the frontend micro-batches any query_batch."""
    idx = indexes["comp"]
    us, rects = workload(graph, 40, extent_ratio=0.05, seed=8)
    with Frontend(idx, max_batch=16, max_delay=1e-3) as fe:
        got = fe.submit_many(us, rects, timeout=30)
    assert (got == idx.query_batch(us, rects)).all()


# ---------------------------------------------------------- dynamic base
def test_dynamic_sharded_base_across_compactions():
    """DynamicIndex(engine="cluster"): sharded base probe under the
    overlay, oracle-checked interleaved mutations across >= 2 compaction
    swaps (each swap repartitions and re-uploads the shards)."""
    from repro.core import build_dynamic_index, rangereach_oracle_batch
    from repro.data import apply_stream_op, streaming_workload
    from repro.dynamic import CompactionPolicy

    g = get_dataset("yelp", scale=0.05)
    dyn = build_dynamic_index(
        g, "2dreach-comp", engine="cluster", n_shards=4,
        policy=CompactionPolicy(max_overlay_edges=30, background=False),
    )
    engines = [dyn.base_engine]     # strong refs: ids must not recycle
    assert isinstance(dyn.base_engine, ShardedEngine)
    assert dyn.base_engine.n_shards == 4
    step = 0
    for op in streaming_workload(g, n_steps=400, seed=31, p_query=0.35,
                                 p_edge=0.45, p_vertex=0.1, p_spatial=0.1):
        apply_stream_op(dyn, op)
        if dyn.base_engine is not engines[-1]:
            engines.append(dyn.base_engine)
        step += 1
        if step % 100 == 0:     # interleaved oracle checks mid-stream
            gm = dyn.snapshot_graph()
            vu, vr = workload(gm, 24, extent_ratio=0.05, seed=step)
            assert (dyn.query_batch(vu, vr)
                    == rangereach_oracle_batch(gm, vu, vr)).all(), step
    assert dyn.stats["n_compactions"] >= 2, \
        "stream too short to cross two compaction swaps"
    assert len(engines) >= 3, \
        "each compaction swap must rebuild the sharded engine"
    gm = dyn.snapshot_graph()
    vu, vr = workload(gm, 64, extent_ratio=0.05, seed=999)
    assert (dyn.query_batch(vu, vr)
            == rangereach_oracle_batch(gm, vu, vr)).all()


# ------------------------------------------- many components, 2dreach-pointer
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


@pytest.fixture(scope="module")
def yelp_pointer():
    """The benchmark's Yelp deployment (``bench/configs/yelp-pointer.json``
    through ``bench/lbsn.py``) cut to ~30K vertices: one giant friend SCC
    beside users with no friends, each with a small tree of its own,
    plus 64 users with no edge at all, which have no tree."""
    sys.path.insert(0, ROOT)
    from bench import lbsn

    with open(os.path.join(ROOT, "bench", "configs", "yelp-pointer.json")) as f:
        cfg = json.load(f)
    g = lbsn.generate(lbsn.scaled(cfg, 30000 / (cfg["users"] + cfg["venues"])),
                      2**31 + 3)
    n = g.n_nodes + 64
    g = lbsn.Graph(n, g.edges, np.concatenate([g.coords,
                                               np.zeros((64, 2), np.float32)]),
                   np.concatenate([g.spatial_mask, np.zeros(64, bool)]))
    index = build_index(make_graph(n, g.edges, g.coords, g.spatial_mask),
                        "2dreach-pointer")
    return g, index


def mixed_tiles(g, index, comp, n_tiles, rng):
    """``n_tiles`` query tiles of ``TB`` lanes, each mixing two users of
    the giant SCC, two users with no friend, two venues and two vertices
    with no tree, under rects of 1-20% of the extent, some on a venue
    and some inverted."""
    from bench import lbsn, traffic

    users = ~g.spatial_mask
    tree = index.lookup_tree(np.arange(g.n_nodes))
    friend = np.zeros(g.n_nodes, bool)
    friend[g.edges[users[g.edges[:, 1]], 0]] = True
    giant = np.argmax(np.bincount(comp[users]))
    kinds = [np.nonzero(users & (comp == giant))[0],
             np.nonzero(users & ~friend & (tree >= 0))[0],
             np.nonzero(g.spatial_mask)[0],
             np.nonzero(users & (tree < 0))[0]]
    assert all(len(k) for k in kinds)
    us = np.stack([rng.choice(k, (n_tiles, 2)) for k in kinds], axis=2)
    us = rng.permuted(us.reshape(n_tiles, TB), axis=1).reshape(-1)
    ext = lbsn.spatial_extent(g)
    ratios = rng.choice([0.01, 0.02, 0.05, 0.10, 0.20], len(us))
    rects = np.concatenate([traffic.region_for_extent(ext, r, 1, rng)
                            for r in ratios])
    on = rng.choice(kinds[2], 16)
    rects[:16] = np.concatenate([g.coords[on], g.coords[on]], axis=1)
    rects[16:32] = rects[16:32][:, [2, 1, 0, 3]]     # xmin > xmax
    rects[32:48] = rects[32:48][:, [0, 3, 2, 1]]     # ymin > ymax
    return us, rects


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_pointer_many_components_match_oracle_and_reference(yelp_pointer,
                                                           impl):
    """The pointer path on the Yelp shape, through the fused XLA program
    and the megakernel (interpret mode on the CPU), against the core
    oracle and the benchmark's own reference, with the candidate counts
    of the two implementations equal."""
    from bench import reference
    from repro.core.oracle import rangereach_oracle_batch

    g, index = yelp_pointer
    ref = reference.Reference(g.n_nodes, g.edges, g.coords, g.spatial_mask)
    us, rects = mixed_tiles(g, index, ref.comp, 32,
                            np.random.default_rng(5))
    want = rangereach_oracle_batch(
        make_graph(g.n_nodes, g.edges, g.coords, g.spatial_mask), us, rects)
    np.testing.assert_array_equal(ref.answers(us, rects), want)
    assert 0 < want.sum() < len(want)
    engines = {}
    for name in {"xla", impl}:
        eng = engines[name] = ShardedEngine(index, n_shards=1)
        eng._fused_impl = name
        np.testing.assert_array_equal(eng.query_batch(us, rects), want)
    assert engines[impl].n_tiles > 1024
    assert engines[impl].stats["tiles_scanned"] == \
        engines["xla"].stats["tiles_scanned"]
    assert engines[impl].stats["trees_routed"] > 32


def test_trees_routed_counts_distinct_trees_per_batch(yelp_pointer):
    """``stats["trees_routed"]`` sums, over batches, the distinct trees the
    real lanes of each batch route to; venues and vertices with no tree
    route to none."""
    from bench import reference

    g, index = yelp_pointer
    comp = reference.Reference(g.n_nodes, g.edges, g.coords,
                               g.spatial_mask).comp
    eng = ShardedEngine(index, n_shards=1)
    rng = np.random.default_rng(6)
    want = 0
    for b in range(3):
        us, rects = mixed_tiles(g, index, comp, 4 + b, rng)
        eng.query_batch(us, rects)
        t = index.lookup_tree(us)
        want += len(set(t[(t >= 0) & ~index.excluded[us]].tolist()))
    assert eng.stats["batches"] == 3
    assert eng.stats["trees_routed"] == want
