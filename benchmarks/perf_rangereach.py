"""§Perf hillclimb: the paper-technique cell (RangeReach query engine).

The paper's own workload runs for real on this host, so this hillclimb
measures wall-clock per query across engine variants and structural
parameters:

    engine    host wavefront | jit wavefront (capacity c) | pallas leaf
              | device (compile-once QueryEngine, hierarchical descent)
    fanout    R-tree node width (VMEM tile shape analogue)
    capacity  jit wavefront frontier budget

plus the build-side closure: per-level scatter-OR vs the bitset_mm
fixpoint (VPU word loop vs MXU unpack-matmul) at growing component
counts.  Each configuration is correctness-checked against the host
engine before timing.

Outputs: results/perf_rangereach.json (full sweep) and a root-level
BENCH_rangereach.json summary tracking the perf trajectory — leaf tiles
scanned by the hierarchical device engine vs the full leaf scan, and the
steady-state recompile / forest-re-transposition counts (both must stay
zero).  ``--smoke`` runs a seconds-scale subset for CI.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List

import numpy as np

from repro import obs
from repro.cluster import ShardedEngine
from repro.core import QueryEngine, build_2dreach, query_host, query_jax_wavefront
from repro.data import get_dataset, workload
from repro.kernels.range_query import ops as rq_ops
from repro.kernels.range_query.ops import range_query_forest
from repro.launch.serve import serve_chunked

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "results", "perf_rangereach.json")
BENCH_OUT = os.path.join(ROOT, "BENCH_rangereach.json")

LAT_BATCH = 256   # chunk size for the per-query latency distribution


def _t(fn, repeats=5):
    fn()  # warmup / compile
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _lat_pct(call, n, batch=LAT_BATCH) -> Dict[str, float]:
    """p50/p95/p99 per-query latency (us) serving [0, n) in chunks.

    ``call(lo, hi)`` serves that query slice and returns its answers;
    the chunked warm-and-measure mechanics (incl. warming the ragged
    tail's jit shape) live in ``repro.launch.serve.serve_chunked``.

    The chunk size is capped so the pass always yields several batches
    — one amortised sample *per batch* goes into the obs ``Histogram``
    (previously n <= batch collapsed to a single chunk whose one value
    made p50 == p95 == p99) — and small runs take extra passes until
    the distribution holds enough batch samples for a stable p99.
    """
    batch = max(1, min(batch, n // 8 or n))
    n_chunks = -(-n // batch)
    passes = max(1, -(-32 // n_chunks))
    hist = obs.Histogram("bench.lat_us", lo=1e-2, hi=1e9)
    for _ in range(passes):
        _, lats, _ = serve_chunked(call, n, batch)
        # one sample per chunk: lats repeats the chunk's amortised
        # per-query latency across its queries — take the chunk heads
        hist.record_many(np.asarray(lats[::batch]) * 1e6)
    return hist.percentile_dict(prefix="lat_p", suffix="_us")


def _stage_profile(run, prefix, cost_fn=None):
    """One instrumented pass *after* the timed one: per-stage span
    totals (µs) plus the kernel cost model, recorded outside the timed
    loop so span overhead never skews ``us_per_q``."""
    was = obs.enabled()
    obs.enable()
    before = obs.stage_totals(prefix)
    run()
    after = obs.stage_totals(prefix)
    if not was:
        obs.disable()
    stage = {k: round(after.get(k, 0.0) - before.get(k, 0.0), 3)
             for k in after
             if after.get(k, 0.0) > before.get(k, 0.0)}
    return stage, (cost_fn() if cost_fn is not None else None)


def engine_sweep(dataset="gowalla", scale=0.5, n_q=2000,
                 fanouts=(8, 16, 32, 64), capacities=(32, 64, 128, 256),
                 repeats=5, n_shards=None) -> List[Dict]:
    g = get_dataset(dataset, scale=scale)
    us, rects = workload(g, n_q, extent_ratio=0.05, seed=5)
    rows = []
    for fanout in fanouts:
        idx = build_2dreach(g, variant="comp", fanout=fanout)
        tid = idx.lookup_tree(us)
        ref = query_host(idx.forest, tid, rects)
        full = idx.query_batch(us, rects)
        # host engine
        dt = _t(lambda: query_host(idx.forest, tid, rects), repeats=repeats)
        rows.append(dict(engine="host", fanout=fanout, capacity=None,
                         us_per_q=dt / n_q * 1e6,
                         depth=idx.forest.depth,
                         **_lat_pct(lambda lo, hi: query_host(
                             idx.forest, tid[lo:hi], rects[lo:hi]), n_q)))
        # jit wavefront at several capacities
        for cap in capacities:
            got, ovf = query_jax_wavefront(idx.forest, tid, rects,
                                           capacity=cap)
            valid = ~np.asarray(ovf)
            assert (np.asarray(got)[valid] == ref[valid]).all()
            ovf_frac = float(np.asarray(ovf).mean())
            dt = _t(lambda: query_jax_wavefront(
                idx.forest, tid, rects, capacity=cap), repeats=repeats)
            rows.append(dict(engine="wavefront", fanout=fanout,
                             capacity=cap, us_per_q=dt / n_q * 1e6,
                             overflow_frac=ovf_frac,
                             depth=idx.forest.depth,
                             **_lat_pct(lambda lo, hi: query_jax_wavefront(
                                 idx.forest, tid[lo:hi], rects[lo:hi],
                                 capacity=cap)[0], n_q)))
        # pallas leaf scan (interpret on CPU — structural comparison)
        got = range_query_forest(idx.forest, tid, rects)
        assert (got == ref).all()
        dt = _t(lambda: range_query_forest(idx.forest, tid, rects),
                repeats=3)
        rows.append(dict(engine="pallas_leafscan", fanout=fanout,
                         capacity=None, us_per_q=dt / n_q * 1e6,
                         depth=idx.forest.depth,
                         **_lat_pct(lambda lo, hi: range_query_forest(
                             idx.forest, tid[lo:hi], rects[lo:hi]), n_q)))
        # device engine: compile-once hierarchical descent
        eng = QueryEngine(idx)
        got = eng.query_batch(us, rects)
        assert (got == full).all(), "device engine disagrees with host"
        # steady-state gates: repeat queries, then assert no new traces
        # and no new host-side forest transpositions
        compiles0 = eng.n_compiles
        soa0 = rq_ops.SOA_BUILDS
        tiles0 = eng.stats["tiles_scanned"]
        grid0 = eng.stats["tiles_grid"]
        full0 = eng.stats["tiles_full_scan"]
        dt = _t(lambda: eng.query_batch(us, rects), repeats=repeats)
        recompiles = eng.n_compiles - compiles0
        retranspositions = rq_ops.SOA_BUILDS - soa0
        batches = eng.stats["batches"] - 1  # minus pre-gate warm batch
        tiles_pb = (eng.stats["tiles_scanned"] - tiles0) / max(batches, 1)
        grid_pb = (eng.stats["tiles_grid"] - grid0) / max(batches, 1)
        full_pb = (eng.stats["tiles_full_scan"] - full0) / max(batches, 1)
        stage_us, cost = _stage_profile(
            lambda: eng.query_batch(us, rects), "engine.",
            lambda: obs.engine_cost_model(eng))
        # retained two-phase path: same answers, separate launches —
        # timed and span-attributed alongside the fused trace so the
        # artifact carries the fusion win per stage
        got2 = eng.query_batch_two_phase(us, rects)
        assert (got2 == full).all(), "two-phase disagrees with host"
        dt2 = _t(lambda: eng.query_batch_two_phase(us, rects),
                 repeats=repeats)
        stage2_us, _ = _stage_profile(
            lambda: eng.query_batch_two_phase(us, rects), "engine.")
        rows.append(dict(
            engine="device", fanout=fanout, capacity=None,
            us_per_q=dt / n_q * 1e6, depth=idx.forest.depth,
            n_leaf_tiles=eng.n_tiles,
            stage_us=stage_us, cost_model=cost,
            two_phase_us_per_q=dt2 / n_q * 1e6,
            two_phase_stage_us=stage2_us,
            tiles_scanned_per_batch=tiles_pb,
            tiles_grid_per_batch=grid_pb,
            tiles_full_scan_per_batch=full_pb,
            steady_state_recompiles=recompiles,
            steady_state_retranspositions=retranspositions,
            **_lat_pct(lambda lo, hi: eng.query_batch(
                us[lo:hi], rects[lo:hi]), n_q),
        ))
        # cluster engine: sharded multi-device serving.  The default
        # (n_shards=None) runs shards == devices — the configuration the
        # cluster<=2x-device ratio gate speaks about; stacked-shard
        # emulation (more shards than devices) stays covered by the
        # cluster tests
        ceng = ShardedEngine(idx, n_shards=n_shards)
        got = ceng.query_batch(us, rects)
        assert (got == full).all(), "cluster engine disagrees with host"
        pct = _lat_pct(lambda lo, hi: ceng.query_batch(
            us[lo:hi], rects[lo:hi]), n_q)
        got2 = ceng.query_batch_two_phase(us, rects)   # warm both paths
        assert (got2 == full).all(), "cluster two-phase disagrees"
        compiles0 = ceng.n_compiles
        soa0 = rq_ops.SOA_BUILDS
        dt = _t(lambda: ceng.query_batch(us, rects), repeats=repeats)
        cstage_us, ccost = _stage_profile(
            lambda: ceng.query_batch(us, rects), "cluster.",
            lambda: obs.engine_cost_model(ceng))
        cdt2 = _t(lambda: ceng.query_batch_two_phase(us, rects),
                  repeats=repeats)
        cstage2_us, _ = _stage_profile(
            lambda: ceng.query_batch_two_phase(us, rects), "cluster.")
        rows.append(dict(
            engine="cluster", fanout=fanout, capacity=None,
            us_per_q=dt / n_q * 1e6, depth=idx.forest.depth,
            n_shards=ceng.n_shards,
            stage_us=cstage_us, cost_model=ccost,
            two_phase_us_per_q=cdt2 / n_q * 1e6,
            two_phase_stage_us=cstage2_us,
            n_devices=int(ceng.mesh.shape["data"]),
            shard_balance=ceng.partition.balance(),
            shard_queries=ceng.shard_queries.tolist(),
            steady_state_recompiles=ceng.n_compiles - compiles0,
            steady_state_retranspositions=rq_ops.SOA_BUILDS - soa0,
            **pct,
        ))
    return rows


def degraded_arm(dataset="gowalla", scale=0.5, n_q=2000, fanout=16,
                 repeats=5) -> Dict:
    """Host-fallback latency under forced degradation (``--degraded``).

    Trips the resilient wrapper's breaker so every query takes the
    exact host descent, the path a dead device degrades to — recording
    what the SLO costs when the accelerator is gone.  Answers are
    asserted bit-identical to the healthy device path first."""
    from repro.resilience import BreakerPolicy, ResilientEngine

    g = get_dataset(dataset, scale=scale)
    us, rects = workload(g, n_q, extent_ratio=0.05, seed=5)
    idx = build_2dreach(g, variant="comp", fanout=fanout)
    res = ResilientEngine(
        QueryEngine(idx), idx,
        breaker=BreakerPolicy(reset_timeout_s=float("inf")))
    healthy = res.query_batch(us, rects)
    dt_dev = _t(lambda: res.query_batch(us, rects), repeats=repeats)
    res.trip()                        # breaker never half-opens again
    got = res.query_batch(us, rects)
    exact = bool((got == healthy).all())
    assert exact, "degraded answers drifted from the device path"
    dt_host = _t(lambda: res.query_batch(us, rects), repeats=repeats)
    pct = _lat_pct(lambda lo, hi: res.query_batch(us[lo:hi],
                                                  rects[lo:hi]), n_q)
    deg_hist = res._h_degraded.snapshot()
    return dict(
        fanout=fanout, n_q=n_q, exact=exact,
        healthy_us_per_q=dt_dev / n_q * 1e6,
        degraded_us_per_q=dt_host / n_q * 1e6,
        degradation_x=dt_host / dt_dev if dt_dev else None,
        fallback_batches=int(res.stats["fallback_batches"]),
        fallback_queries=int(res.stats["fallback_queries"]),
        degraded_hist_count=int(deg_hist["count"]),
        **pct)


def closure_sweep(scales=(0.1, 0.25, 0.5)) -> List[Dict]:
    """Build-side: per-level scatter-OR vs bitset-matmul fixpoint."""
    from repro.core import condense, scc_np
    from repro.core.reachability import closure_np, pack_rows
    from repro.kernels.bitset_mm.ops import closure_fixpoint

    rows = []
    for scale in scales:
        g = get_dataset("yelp", scale=scale)
        labels = scc_np(g.n_nodes, g.edges)
        cond = condense(g.n_nodes, g.edges, labels)
        t0 = time.perf_counter()
        clo = closure_np(cond, g.n_nodes, g.spatial_ids)
        t_np = time.perf_counter() - t0
        d, p = cond.n_comps, clo.p
        rows.append(dict(method="scatter_or_levels", scale=scale,
                         n_comps=d, n_spatial=p, seconds=t_np))
        if d <= 12000:
            # dense closure paths only feasible at small d
            own = np.zeros((d, p), dtype=bool)
            own[np.repeat(np.arange(d), np.diff(clo.own_indptr)),
                clo.own_cols] = True
            A = np.zeros((d, d), dtype=bool)
            if cond.dag_edges.size:
                A[cond.dag_edges[:, 0], cond.dag_edges[:, 1]] = True
            ob, ab = pack_rows(own), pack_rows(A)
            t0 = time.perf_counter()
            closure_fixpoint(ob, ab, n_iters=cond.n_levels + 1,
                             use_mxu=True)
            rows.append(dict(method="bitset_mm_mxu", scale=scale,
                             n_comps=d, n_spatial=p,
                             seconds=time.perf_counter() - t0))
    return rows


def bench_summary(engine_rows: List[Dict]) -> Dict:
    """Root-level perf-trajectory datapoint (BENCH_rangereach.json)."""
    device = [r for r in engine_rows if r["engine"] == "device"]
    cluster = [r for r in engine_rows if r["engine"] == "cluster"]
    best = {}
    pct = {}
    for name in ("host", "wavefront", "pallas_leafscan", "device",
                 "cluster"):
        cands = [r for r in engine_rows if r["engine"] == name]
        if cands:
            best[name] = min(r["us_per_q"] for r in cands)
            winner = min(cands, key=lambda r: r["us_per_q"])
            if "lat_p50_us" in winner:
                pct[name] = {p: winner[f"lat_{p}_us"]
                             for p in ("p50", "p95", "p99")}
    scanned = sum(r["tiles_scanned_per_batch"] for r in device)
    grid = sum(r["tiles_grid_per_batch"] for r in device)
    full = sum(r["tiles_full_scan_per_batch"] for r in device)

    def _winner_stages(rows):
        if not rows:
            return None
        w = min(rows, key=lambda r: r["us_per_q"])
        out = {"stage_us": w.get("stage_us"),
               "cost_model": w.get("cost_model")}
        if w.get("two_phase_us_per_q") is not None:
            # fused-vs-two-phase attribution: the same engine serving
            # the same workload through the retained two-launch path
            out["fused_us_per_q"] = w["us_per_q"]
            out["two_phase_us_per_q"] = w["two_phase_us_per_q"]
            out["two_phase_stage_us"] = w.get("two_phase_stage_us")
            out["fusion_speedup_x"] = (
                w["two_phase_us_per_q"] / w["us_per_q"]
                if w["us_per_q"] else None)
        return out

    return {
        "schema_version": 2,
        "unit": "us_per_query (best over structural params)",
        "engines": best,
        # per-stage host-span attribution + kernel cost model of the
        # best device / cluster configurations (additive in v2)
        "per_stage": {
            "device": _winner_stages(device),
            "cluster": _winner_stages(cluster),
        },
        "latency_percentiles_us": pct,
        "cluster_engine": {
            "n_shards": cluster[0]["n_shards"] if cluster else None,
            "n_devices": cluster[0]["n_devices"] if cluster else None,
            "shard_balance": max(
                (r["shard_balance"] for r in cluster), default=None),
            "steady_state_recompiles": int(sum(
                r["steady_state_recompiles"] for r in cluster)),
            "steady_state_retranspositions": int(sum(
                r["steady_state_retranspositions"] for r in cluster)),
        },
        "hierarchical_device_engine": {
            "leaf_tiles_scanned_per_batch": scanned,
            "grid_steps_per_batch_incl_bucket_padding": grid,
            "leaf_tiles_full_scan_per_batch": full,
            "scan_fraction": scanned / full if full else None,
            "strictly_fewer_than_full_scan": bool(scanned < full),
            "steady_state_recompiles": int(sum(
                r["steady_state_recompiles"] for r in device)),
            "steady_state_retranspositions": int(sum(
                r["steady_state_retranspositions"] for r in device)),
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale subset for CI: one fanout/"
                         "capacity, small dataset, no closure sweep")
    ap.add_argument("--degraded", action="store_true",
                    help="also time the exact host-fallback path with "
                         "the breaker tripped (additive 'degraded' "
                         "field in BENCH_rangereach.json)")
    args = ap.parse_args()

    if args.smoke:
        engines = engine_sweep(dataset="yelp", scale=0.1, n_q=256,
                               fanouts=(16,), capacities=(64,), repeats=2)
        closure = closure_sweep(scales=(0.1,))
    else:
        engines = engine_sweep()
        closure = closure_sweep()
    degraded = None
    if args.degraded:
        degraded = (degraded_arm(dataset="yelp", scale=0.1, n_q=256,
                                 repeats=2)
                    if args.smoke else degraded_arm())
    out = {"engine_sweep": engines, "closure": closure}
    if degraded is not None:
        out["degraded"] = degraded
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    summary = bench_summary(engines)
    if degraded is not None:
        # additive field: schema_version stays 2, consumers of the
        # existing keys are unaffected
        summary["degraded"] = degraded
    with open(BENCH_OUT, "w") as f:
        json.dump(summary, f, indent=1)
    for r in engines:
        print(r)
    for r in closure:
        print(r)
    print(json.dumps(summary, indent=1))
    dev = summary["hierarchical_device_engine"]
    assert dev["strictly_fewer_than_full_scan"], \
        "hierarchical engine failed to prune any leaf tiles"
    assert dev["steady_state_recompiles"] == 0, "steady-state recompile"
    assert dev["steady_state_retranspositions"] == 0, \
        "steady-state host-side forest re-transposition"
    clu = summary["cluster_engine"]
    assert clu["steady_state_recompiles"] == 0, \
        "cluster steady-state recompile"
    assert clu["steady_state_retranspositions"] == 0, \
        "cluster steady-state host-side forest re-transposition"
    assert all("p99" in v for v in
               summary["latency_percentiles_us"].values()), \
        "latency percentiles missing from the bench summary"
    if degraded is not None:
        assert degraded["exact"], "degraded arm must stay bit-identical"
        assert degraded["fallback_queries"] > 0, \
            "degraded arm never reached the host fallback"


if __name__ == "__main__":
    main()
