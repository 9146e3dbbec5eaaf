"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--scale 0.5]
                                            [--queries 400]

Sections:
    [table2] graph/SCC statistics per dataset vs the paper's structure
    [table3] index construction time (5 methods x 4 datasets) + claims
    [table4] index size decomposition + claims
    [fig3]   query-time sweeps (3 parameters x 6 methods x 4 datasets)
             + the stability ratio behind the paper's headline claim
    [kernels] Pallas kernel microbenches (interpret mode on CPU)
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def _section(name):
    print(f"\n===== [{name}] " + "=" * (60 - len(name)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--queries", type=int, default=None)
    ap.add_argument("--skip-fig3", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    scale = args.scale or (0.5 if args.full else 0.25)
    n_q = args.queries or (1000 if args.full else 400)

    from . import paper_fig3, paper_tables

    t_start = time.perf_counter()

    _section("table2: graph + SCC statistics (scaled synthetic vs paper)")
    for row in paper_tables.table2(scale):
        print(
            f"{row['dataset']:<11} nodes={row['nodes']:>7} "
            f"edges={row['edges']:>8} sccs={row['sccs']:>7} "
            f"user_sccs={row['user_sccs']:>7} "
            f"({row['ours_user_scc_pct']:>5.1f}% ours vs "
            f"{row['paper_user_scc_pct']:>5.1f}% paper) "
            f"distinct_rtrees={row['distinct_rtrees']}"
        )

    _section("table3: index construction time [secs]")
    t3 = paper_tables.table3(scale)
    methods = [k for k in t3[0] if k != "dataset"]
    print(f"{'dataset':<12}" + "".join(f"{m:>18}" for m in methods))
    for row in t3:
        print(f"{row['dataset']:<12}"
              + "".join(f"{row[m]:>18.3f}" for m in methods))

    _section("table4: index size [MB] (rtree/aux)")
    t4 = paper_tables.table4(scale)
    t4raw = paper_tables.table4_raw(scale)
    print(f"{'dataset':<12}" + "".join(f"{m:>22}" for m in methods))
    for row in t4:
        print(f"{row['dataset']:<12}"
              + "".join(f"{row[m]:>22}" for m in methods))

    _section("paper claims")
    for line in paper_tables.check_claims(t3, t4raw):
        print(line)

    if not args.skip_fig3:
        _section("fig3: query time sweeps [us/query]")
        all_rows = []
        for ds in paper_fig3.DATASETS:
            rows = paper_fig3.sweep(ds, scale, n_queries=n_q, repeats=2)
            all_rows.extend(rows)
            for r in rows:
                vals = "".join(
                    f"{r[m]:>12.2f}" for m in paper_fig3.METHODS)
                print(f"{ds:<11} {r['param']:<12}{str(r['value']):<10}"
                      + vals)
            stab = paper_fig3.stability(rows)
            print(f"{ds:<11} stability max/min ratio: "
                  + ", ".join(f"{m}={v}" for m, v in stab.items()))

    _section("kernel microbenches (interpret mode — correctness-scale)")
    _kernel_bench()

    print(f"\n[benchmarks] total {time.perf_counter() - t_start:.1f}s")


def _kernel_bench():
    import jax.numpy as jnp

    from repro.core import build_forest, query_host
    from repro.data import get_dataset, workload
    from repro.core import build_index
    from repro.kernels.range_query.ops import range_query_forest

    g = get_dataset("gowalla", scale=0.1)
    idx = build_index(g, "2dreach-comp")
    us, rects = workload(g, 512, seed=3)
    tid = idx.lookup_tree(us)
    for name, fn in (
        ("host_wavefront", lambda: query_host(idx.forest, tid, rects)),
        ("pallas_leafscan(interp)",
         lambda: range_query_forest(idx.forest, tid, rects)),
    ):
        fn()
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        print(f"{name:<26} {dt / len(us) * 1e6:>9.2f} us/query "
              f"({len(us)} queries)")

    from repro.core.reachability import pack_rows
    from repro.kernels.bitset_mm.ops import bitset_mm_mxu

    rng = np.random.default_rng(0)
    d = 512
    A = pack_rows(rng.random((d, d)) < 0.01)
    R = pack_rows(rng.random((d, 2048)) < 0.05)
    bitset_mm_mxu(A, R)
    t0 = time.perf_counter()
    bitset_mm_mxu(A, R)
    dt = time.perf_counter() - t0
    print(f"{'bitset_mm_mxu d=512':<26} {dt * 1e3:>9.2f} ms/iter")


if __name__ == "__main__":
    main()
