"""Data substrate: LBSN shaping and query workloads."""

import numpy as np

from repro.data import dataset_stats, get_dataset, workload


def test_lbsn_shapes_match_paper_structure():
    """The knob that matters: SCC structure per dataset (paper Table 2)."""
    gow = get_dataset("gowalla", scale=0.1)
    s = dataset_stats(gow)
    assert s["user_sccs"] <= 3  # paper: 1 (one giant social SCC)
    yelp = get_dataset("yelp", scale=0.1)
    s2 = dataset_stats(yelp)
    assert s2["user_sccs"] / s2["sccs"] > 0.5  # paper: 87.9%
    assert s2["users"] / s2["nodes"] > 0.85    # paper: 93% users
    assert s["venues"] / s["nodes"] > 0.8      # paper: 87% venues
    # venues are sinks in the LBSN model
    assert gow.spatial_sink_mask().sum() == gow.n_spatial


def test_workload_parameters():
    g = get_dataset("yelp", scale=0.1)
    us, rects = workload(g, n_queries=100, extent_ratio=0.05, seed=0)
    ext = g.spatial_extent()
    area = (ext[2] - ext[0]) * (ext[3] - ext[1])
    qarea = (rects[:, 2] - rects[:, 0]) * (rects[:, 3] - rects[:, 1])
    np.testing.assert_allclose(qarea, 0.05 * area, rtol=1e-3)
    # selectivity-targeted regions contain ~k venues
    us2, rects2 = workload(g, n_queries=20, selectivity=0.001, seed=0)
    pts = g.coords[g.spatial_mask]
    k = round(0.001 * g.n_nodes)
    for r in rects2:
        inside = ((pts[:, 0] >= r[0]) & (pts[:, 0] <= r[2])
                  & (pts[:, 1] >= r[1]) & (pts[:, 1] <= r[3])).sum()
        assert inside >= k  # grown to cover at least k

