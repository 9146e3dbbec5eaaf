"""The benchmark harness on the CPU at small sizes: spec resolution, the
refusal without a TPU, the copied generator, the reference, the byte
count of the roofline, and whole runs of every cell."""

import json
import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from bench import lbsn, reference, roofline, spec, traffic  # noqa: E402
from bench import run as bench_run  # noqa: E402

SPEC = spec.load()
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def cut(cfg, nodes):
    """``cfg`` cut to about ``nodes`` vertices, its shape kept."""
    return lbsn.scaled(cfg, nodes / (cfg["users"] + cfg["venues"]))


def tiny_cell(name, nodes=20000):
    """A cell as BENCHMARK.json gives it, cut to ``nodes`` vertices, with
    an open loop's rate cut to what the CPU path serves."""
    c = spec.cell(name)
    c["config"] = cut(c["config"], nodes)
    if c["traffic"]["loop"] == "open":
        c["traffic"] = dict(c["traffic"], rate_qps=400.0)
    return c


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    c = spec.cell(name)
    assert c["config"]["name"] == c["workload"]["config"]
    assert c["traffic"]["loop"] in ("open", "closed")
    for m in c["end_to_end"] + c["per_layer"]:
        assert callable(spec.reader(m["name"]))
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"]


def test_spec_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for p in SPEC["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) <= set(cfg)
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + list(used)
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))


def test_run_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out


def small_cfg(nodes=20000):
    return cut(spec.cell(CELLS[0])["config"], nodes)


def test_generator_repeats_per_seed():
    a = lbsn.generate(small_cfg(), 2**31 + 5)
    b = lbsn.generate(small_cfg(), 2**31 + 5)
    c = lbsn.generate(small_cfg(), 7)
    for x, y in ((a.edges, b.edges), (a.coords, b.coords),
                 (a.spatial_mask, b.spatial_mask)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.coords, c.coords)
    # the structure is the configuration's: one seed relabels it
    np.testing.assert_array_equal(np.sort(lbsn.out_degree(a)),
                                  np.sort(lbsn.out_degree(c)))


def test_generator_has_gowallas_shape():
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components

    cfg = small_cfg()
    g = lbsn.generate(cfg, 3)
    n = g.n_nodes
    assert (n, int(g.spatial_mask.sum())) == (cfg["users"] + cfg["venues"],
                                              cfg["venues"])
    key = g.edges[:, 0] * n + g.edges[:, 1]
    assert len(np.unique(key)) == len(key) == cfg["edges"]
    assert not g.spatial_mask[g.edges[:, 0]].any()   # venues are sinks
    adj = csr_matrix((np.ones(len(g.edges), bool),
                      (g.edges[:, 0], g.edges[:, 1])), shape=(n, n))
    n_comp, comp = connected_components(adj, directed=True,
                                        connection="strong")
    with_venue = np.zeros(n_comp, bool)
    with_venue[comp[g.spatial_mask]] = True
    assert int((~with_venue).sum()) == 1          # Table 2: 1 user SCC
    assert n_comp == g.spatial_mask.sum() + 1     # SCCs = venues + 1
    # every venue is checked in, so the user SCC reaches all of them
    assert np.unique(g.edges[g.spatial_mask[g.edges[:, 1]], 1]).size == \
        cfg["venues"]


# Yelp at arXiv:2602.05928's Table-2 counts: mutual friendships make the
# SCCs the friend graph's components, one giant and about a million
# isolated users
YELP = {"users": 1987693, "venues": 150310, "edges": 21357271,
        "social_avg_deg": 0.58, "reciprocity": 1.0, "zipf_users": 1.3,
        "zipf_venues": 1.2, "n_cities": 12, "city_sigma": 3.0,
        "graph_seed": 14}
SHAPES = {"gowalla": lambda: small_cfg(3000), "yelp": lambda: cut(YELP, 3000)}


def queries_of_every_kind(g, rng):
    """Vertices of every out-degree (venues and users with no edge among
    them) and random ones, with rects of 1-20% of the extent, some on a
    venue and some inverted."""
    deg = lbsn.out_degree(g)
    perm = rng.permutation(g.n_nodes)
    _, first = np.unique(deg[perm], return_index=True)
    us = np.concatenate([perm[first], rng.integers(0, g.n_nodes, 200)])
    ext = lbsn.spatial_extent(g)
    rects = np.concatenate([
        traffic.region_for_extent(ext, r, len(us), rng)
        for r in (0.01, 0.02, 0.05, 0.10, 0.20)])
    us = np.tile(us, 5)
    venues = np.nonzero(g.spatial_mask)[0]
    on = rng.choice(venues, 40)
    rects[:40] = np.concatenate([g.coords[on], g.coords[on]], axis=1)
    rects[40:80] = rects[40:80][:, [2, 1, 0, 3]]     # xmin > xmax
    rects[80:120] = rects[80:120][:, [0, 3, 2, 1]]   # ymin > ymax
    return us, rects


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_reference_agrees_with_core_oracle(shape):
    from repro.core.graph import make_graph
    from repro.core.oracle import rangereach_oracle_batch

    g = lbsn.generate(SHAPES[shape](), 11)
    us, rects = queries_of_every_kind(g, np.random.default_rng(0))
    want = rangereach_oracle_batch(
        make_graph(g.n_nodes, g.edges, g.coords, g.spatial_mask), us, rects)
    ref = reference.Reference(g.n_nodes, g.edges, g.coords, g.spatial_mask)
    got = ref.answers(us, rects)
    np.testing.assert_array_equal(got, want)
    assert 0 < want.sum() < len(want)
    if shape == "yelp":
        assert len(np.unique(ref.comp[us])) > 50    # many components


def scipy_answers(g, us, rects, precision):
    """The reference as it stood before it searched on its own arrays:
    scipy's ``breadth_first_order`` per queried component, then the
    grid over that component's venues."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import breadth_first_order, connected_components

    n = g.n_nodes
    adj = csr_matrix((np.ones(len(g.edges), bool),
                      (g.edges[:, 0], g.edges[:, 1])), shape=(n, n))
    _, comp = connected_components(adj, directed=True, connection="strong")
    rects = reference._round(rects, precision)
    out = np.zeros(len(us), dtype=bool)
    qc = comp[us]
    for c in np.unique(qc):
        sel = np.nonzero(qc == c)[0]
        seen = breadth_first_order(adj, int(us[sel[0]]), directed=True,
                                   return_predecessors=False)
        venues = seen[g.spatial_mask[seen]]
        out[sel] = reference.any_point_in_rects(
            reference._round(g.coords[venues], precision), rects[sel])
    return out


# (DIRECT_PAIRS, DENSE_SHARE): every component on the grid and every
# frontier gathered; each chosen by size; every component compared pair by
# pair and every frontier expanded over all edges
PATHS = {"grid-gathered": (-1, 0), "mixed": (1000, 8),
         "pairs-dense": (1 << 62, 1 << 62)}


@pytest.mark.parametrize("paths", sorted(PATHS))
@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_reference_matches_per_component_scipy_path(shape, precision,
                                                    paths, monkeypatch):
    pairs, dense = PATHS[paths]
    monkeypatch.setattr(reference, "DIRECT_PAIRS", pairs)
    monkeypatch.setattr(reference, "DENSE_SHARE", dense)
    g = lbsn.generate(SHAPES[shape](), 12)
    us, rects = queries_of_every_kind(g, np.random.default_rng(1))
    ref = reference.Reference(g.n_nodes, g.edges, g.coords, g.spatial_mask)
    got = ref.answers(us, rects, precision=precision)
    np.testing.assert_array_equal(got, scipy_answers(g, us, rects,
                                                     precision))
    assert not ref.seen.any()          # every mark reset after its search
    assert 0 < got.sum() < len(got)


def test_any_point_in_rects_matches_brute_force():
    rng = np.random.default_rng(1)
    pts = (rng.standard_normal((3000, 2)) * 3 + 50).astype(np.float32)
    rects = traffic.region_for_extent(
        np.array([40, 40, 60, 60], np.float32), 0.002, 4000, rng)
    rects[:50] = np.concatenate([pts[:50], pts[:50]], axis=1)  # on a point
    want = np.array([np.any((pts[:, 0] >= r[0]) & (pts[:, 0] <= r[2])
                            & (pts[:, 1] >= r[1]) & (pts[:, 1] <= r[3]))
                     for r in rects])
    np.testing.assert_array_equal(reference.any_point_in_rects(pts, rects),
                                  want)


@pytest.mark.parametrize("method", ["2dreach", "2dreach-comp"])
def test_roofline_bytes_equal_brute_force(method):
    from repro.core import build_index
    from repro.core.graph import make_graph

    g = lbsn.generate(small_cfg(4000), 5)
    index = build_index(
        make_graph(g.n_nodes, g.edges, g.coords, g.spatial_mask), method)
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


def test_peaks_refuse_an_unknown_device():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


@pytest.mark.parametrize("name", CELLS)
def test_cpu_run_of_each_cell_is_correct(name):
    res = bench_run.run_cell(tiny_cell(name), 2**31 + 17, 1.0, False,
                             {"platform": "cpu"}, on_chip=False)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 100 and res["failed"] == 0
    assert "setup_s" in res["metrics"]
    assert list(res)[-1] == "checks"


def test_sweep_reports_one_rate():
    from bench import sweep

    cell = tiny_cell(CELLS[0])
    served = bench_run.Served(cell, 3, on_chip=False)
    try:
        served.warm(cell["traffic"], 3)
        r = sweep.one_rate(served, cell["traffic"], 300.0, 0.5, 3)
    finally:
        served.close()
    assert r["sent"] > 50 and r["backlog_end"] >= 0
    assert 0 < r["p50_ms"] <= r["p99_ms"]
