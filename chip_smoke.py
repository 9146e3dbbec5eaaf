"""Smoke run of RangeReach serving on a TPU: build, serve, check.

    python chip_smoke.py             # one chip (what CI on the chip runs)
    python chip_smoke.py --chips 4   # the 4-shard collective path only

One chip: generate Gowalla x50 (3.1M vertices) from its seed, build
``2dreach`` on the host, then serve through the entry points a user
calls — ``Frontend`` over ``ShardedEngine`` (one shard per device) for
RangeReach, ``engine_for`` for RangeCount / RangeCollect — on the fused
Pallas megakernel, and check every answer against the host descent and
a sample against the BFS oracle.  Then the ``backend="device"`` build
(Pallas closure + forest bulk-load) must equal the host build bit for
bit and be adopted by the engine without a copy.

``--chips 4``: ``ShardedEngine(n_shards=4)`` behind ``Frontend`` on a
4-device mesh, one arena per chip, compared with the one-chip
``QueryEngine`` and the host answers.

Exits nonzero, printing no result, when JAX finds no TPU.  Any failed
check raises.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
One process; it starts no other.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

DATASET = "gowalla"
SCALE = 50.0              # 3.1M vertices: the paper's Table 2 vertex count
METHOD = "2dreach"
# the device build's level frontier is a dense (destinations x venue
# words) bitset: 2.4 GB at x10, 47 GB at x50 (16 GB HBM on one v5e)
DEVICE_BUILD_SCALE = 10.0
N_QUERIES = 1024
BATCH = 256
K = 10                    # RangeCollect cap
N_ORACLE = 16


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(n_chips: int) -> dict:
    """The device as JAX reports it; exits nonzero unless it is a TPU
    with at least ``n_chips`` chips."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"devices: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{info['platform']!r}); this smoke runs on a TPU only")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} TPU "
                 f"devices, found {len(devs)}")
    return info


def require_megakernel(eng) -> None:
    """The engine serves through the compiled Pallas megakernel."""
    mode = (eng._fused_impl, eng._interpret)
    log(f"{type(eng).__name__}: fused_impl={mode[0]}, interpret={mode[1]}")
    assert mode == ("pallas", False), mode


def build_host(scale: float, method: str = METHOD):
    """Generate the dataset from its seed and build ``method`` on the
    host.  Returns ``(graph, index)``."""
    from repro.core import build_index, index_nbytes
    from repro.data import get_dataset

    t0 = time.perf_counter()
    g = get_dataset(DATASET, scale=scale)
    t1 = time.perf_counter()
    index = build_index(g, method)
    t2 = time.perf_counter()
    log(f"{DATASET} x{scale:g}: {g.n_nodes} vertices, {g.n_edges} edges, "
        f"{g.n_spatial} venues (generated in {t1 - t0:.1f} s)")
    log(f"{method} host build {t2 - t1:.1f} s: "
        f"{len(index.forest.entries)} leaf entries, "
        f"{index.forest.n_trees} trees, "
        f"{index_nbytes(index)['total']} index bytes")
    return g, index


def mismatches(got, want) -> int:
    return int(np.sum(np.asarray(got) != np.asarray(want)))


def serve_reach(index, us, rects, *, n_shards: int, batch: int):
    """RangeReach through ``Frontend`` over ``ShardedEngine`` — the
    ``launch/serve.py --engine cluster`` path.  Warms every batch bucket
    and the capacity high-water mark, then serves the stream again and
    asserts that pass compiled nothing and re-ran nothing.  Returns
    ``(answers, engine)``."""
    from repro.cluster import Frontend, ShardedEngine

    eng = ShardedEngine(index, n_shards=n_shards)
    part = eng.partition
    log(f"ShardedEngine: {eng.n_shards} shard(s) on "
        f"{eng.mesh.shape['data']} device(s), per-shard entries "
        f"{part.shard_entries.tolist()}")
    # deadline well above the submit time of a full batch, so the
    # stream flushes as full batches in both passes
    fe = Frontend(eng, max_batch=batch, max_delay=0.05)
    try:
        fe.warmup(us[:batch], rects[:batch])
        fe.submit_many(us, rects, timeout=600)   # ratchets the capacity
        fe.warmup(us[:batch], rects[:batch])     # re-pin every bucket
        warm = eng.n_compiles
        reruns = eng.stats["fused_reruns"]
        t0 = time.perf_counter()
        ans = fe.submit_many(us, rects, timeout=600)
        dt = time.perf_counter() - t0
    finally:
        fe.close(timeout=60)
    log(f"reach: {len(us)} queries through Frontend, {warm} compiled "
        f"shapes after warmup, {eng.n_compiles - warm} compiles and "
        f"{eng.stats['fused_reruns'] - reruns} capacity re-runs in the "
        f"steady pass ({dt:.3f} s)")
    assert eng.n_compiles == warm, (eng.n_compiles, warm)
    assert eng.stats["fused_reruns"] == reruns
    return ans, eng


def serve_analytics(index, us, rects, *, k: int):
    """One batch each of RangeCount and RangeCollect through
    ``engine_for(index, required=True)`` (warm call, then a steady call
    that must compile and re-run nothing).  Returns ``(counts, collect,
    engine)`` of the steady call."""
    from repro.core import engine_for

    eng = engine_for(index, required=True)
    eng.count_batch(us, rects)
    eng.collect_batch(us, rects, k)
    warm, reruns = eng.n_compiles, eng.stats["fused_reruns"]
    t0 = time.perf_counter()
    counts = eng.count_batch(us, rects)          # host arrays: synced
    col = eng.collect_batch(us, rects, k)
    dt = time.perf_counter() - t0
    log(f"count + collect(k={k}): {len(us)} queries each, "
        f"{eng.n_compiles - warm} compiles and "
        f"{eng.stats['fused_reruns'] - reruns} re-runs in the steady "
        f"call ({dt:.3f} s)")
    assert eng.n_compiles == warm and eng.stats["fused_reruns"] == reruns
    return counts, col, eng


def check_answers(g, index, us, rects, reach, counts, col, *, k: int,
                  n_oracle: int) -> None:
    """Every answer against the host path; ``n_oracle`` of each class
    against the BFS oracles."""
    from repro.core import batch_query, rangereach_oracle_batch, run_queries
    from repro.core.oracle import range_collect_oracle, range_count_oracle
    from repro.queries import QueryProgram

    host = batch_query(index, us, rects)
    m = mismatches(reach, host)
    log(f"reach vs host: {m} mismatches of {len(us)} "
        f"({int(np.sum(host))} positive)")
    assert m == 0
    B = len(counts)
    hc = run_queries(index, QueryProgram.count(us[:B], rects[:B]))
    hl = run_queries(index, QueryProgram.collect(us[:B], rects[:B], k))
    mc = mismatches(counts, hc)
    ml = (mismatches(col.ids, hl.ids) + mismatches(col.counts, hl.counts)
          + mismatches(col.overflow, hl.overflow))
    log(f"count vs host: {mc} mismatches of {B}; collect vs host: {ml}")
    assert mc == 0 and ml == 0
    n = n_oracle
    mo = mismatches(reach[:n], rangereach_oracle_batch(g, us[:n], rects[:n]))
    mco = sum(int(counts[i] != range_count_oracle(g, int(us[i]), rects[i]))
              for i in range(n))
    mlo = 0
    for i in range(n):
        want = range_collect_oracle(g, int(us[i]), rects[i])
        mlo += int(col.counts[i] != len(want)
                   or not np.array_equal(col.row(i), want[:k]))
    log(f"vs BFS oracle ({n} each): reach {mo}, count {mco}, "
        f"collect {mlo} mismatches")
    assert mo == 0 and mco == 0 and mlo == 0


def forest_diff(a, b) -> list:
    """Names of the index arrays in which two builds differ."""
    fa, fb = a.forest, b.forest
    pairs = {"entries": (fa.entries, fb.entries),
             "entry_ids": (fa.entry_ids, fb.entry_ids),
             "entry_off": (fa.entry_off, fb.entry_off),
             "comp_tree": (a.comp_tree, b.comp_tree),
             "depth": (fa.depth, fb.depth)}
    for lv in range(min(fa.depth, fb.depth)):
        pairs[f"level_mbr[{lv}]"] = (fa.level_mbr[lv], fb.level_mbr[lv])
        pairs[f"tree_off[{lv}]"] = (fa.tree_off[lv], fb.tree_off[lv])
    return [k for k, (x, y) in pairs.items() if not np.array_equal(x, y)]


def device_build(scale: float, *, n_queries: int, method: str = METHOD):
    """``backend="device"`` build vs the host build of the same graph:
    bit-identical forest, zero-copy adoption by ``QueryEngine``, and the
    adopted engine's answers equal to host.  Returns the device index."""
    import jax

    from repro.core import QueryEngine, batch_query, build_index
    from repro.core.engine import UPLOAD_COUNTERS
    from repro.data import get_dataset, workload

    g = get_dataset(DATASET, scale=scale)
    host = build_index(g, method)
    t0 = time.perf_counter()
    dev = build_index(g, method, backend="device")
    jax.block_until_ready(dev.forest.device.entries)
    dt = time.perf_counter() - t0
    diff = forest_diff(host, dev)
    log(f"device build at {DATASET} x{scale:g} ({g.n_nodes} vertices, "
        f"{len(dev.forest.entries)} entries) in {dt:.1f} s: forest "
        + (f"differs from the host build in {diff}" if diff
           else "bit-identical to the host build"))
    assert not diff, diff
    before = dict(UPLOAD_COUNTERS)
    eng = QueryEngine(dev)
    after = dict(UPLOAD_COUNTERS)
    log(f"device index served with adopted={eng.stats['adopted']}, "
        f"host uploads +{after['host_uploads'] - before['host_uploads']}")
    assert eng.stats["adopted"] == 1
    assert after["host_uploads"] == before["host_uploads"]
    us, rects = workload(g, n_queries=n_queries, seed=2)
    m = mismatches(eng.query_batch(us, rects), batch_query(host, us, rects))
    log(f"device-built index reach vs host build: {m} mismatches of "
        f"{len(us)}")
    assert m == 0
    return dev


def sharded_vs_single(index, us, rects, *, n_shards: int, batch: int):
    """The sharded collective path across chips against the one-chip
    engine and the host answers."""
    from repro.core import batch_query, engine_for

    ans, eng = serve_reach(index, us, rects, n_shards=n_shards,
                           batch=batch)
    devs = eng.arena_devices()
    for s, d in enumerate(devs):
        log(f"shard {s}: arena on {d}")
    assert len(set(devs)) == n_shards, devs
    one = engine_for(index, required=True).query_batch(us, rects)
    host = batch_query(index, us, rects)
    m1, mh = mismatches(ans, one), mismatches(ans, host)
    log(f"{n_shards}-shard reach vs one-chip engine: {m1} mismatches, "
        f"vs host: {mh} ({len(us)} queries)")
    assert m1 == 0 and mh == 0
    return eng


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-shard sharded path")
    args = ap.parse_args(argv)
    info = require_tpu(args.chips)

    from repro.data import workload
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    g, index = build_host(SCALE)
    us, rects = workload(g, n_queries=N_QUERIES, seed=1)
    if args.chips == 4:
        require_megakernel(sharded_vs_single(
            index, us, rects, n_shards=4, batch=BATCH))
    else:
        reach, eng = serve_reach(index, us, rects, n_shards=1,
                                 batch=BATCH)
        require_megakernel(eng)
        counts, col, qeng = serve_analytics(index, us[:BATCH],
                                            rects[:BATCH], k=K)
        require_megakernel(qeng)
        check_answers(g, index, us, rects, reach, counts, col, k=K,
                      n_oracle=N_ORACLE)
        device_build(DEVICE_BUILD_SCALE, n_queries=BATCH)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
