"""repro.obs: tracer, metrics registry, query log, profiler hooks.

Covers the observability acceptance gates:

* Histogram percentiles are bit-for-bit ``np.percentile`` on replayed
  latency samples (the unified path behind ``launch/serve.py`` and
  ``benchmarks/perf_rangereach.py``), degrading gracefully once the
  exact window saturates.
* The span tracer is thread-safe, bounded, emits valid Chrome-trace
  events, and its interval-union coverage attributes >=95% of a mixed
  engine+frontend serve to instrumented layers.
* ``CounterDict`` keeps the legacy dict surfaces
  (``engine.UPLOAD_COUNTERS``) live against the registry.
* The structured query log stays bounded with eviction-proof
  aggregates and exports valid JSONL.
* ``batch_query(engine="device")`` host fallback warns once *per
  (reason, index type)* and counts every fallback in the registry.
"""

from __future__ import annotations

import json
import threading
import time
import warnings

import numpy as np
import pytest

from conftest import random_geosocial, random_queries
from repro import obs
from repro.obs.metrics import CounterDict, Histogram, Registry
from repro.obs.querylog import (
    FIELDS,
    I_VERTEX_CLASS,
    QueryLog,
    SCHEMA_VERSION,
    rect_bucket,
)
from repro.obs.tracer import Tracer


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with obs disabled and empty."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(scope="module")
def built():
    rng = np.random.default_rng(7)
    g = random_geosocial(rng, 400, 1200)
    from repro.core import QueryEngine, build_2dreach

    idx = build_2dreach(g, variant="comp")
    eng = QueryEngine(idx)
    us, rects = random_queries(rng, g, 128)
    return g, idx, eng, us, rects


# ---------------------------------------------------------------- metrics

def test_histogram_bit_for_bit_percentiles():
    rng = np.random.default_rng(3)
    for sample in (rng.lognormal(3.0, 1.0, 5000),
                   rng.random(1000) * 1e6,
                   np.array([42.0]),
                   rng.exponential(10.0, 257)):
        h = Histogram.from_samples(sample)
        assert not h.saturated
        for p in (0, 25, 50, 90, 95, 99, 99.9, 100):
            assert h.percentile(p) == float(np.percentile(sample, p)), \
                f"p{p} diverged from np.percentile"


def test_histogram_legacy_key_shapes():
    lat = np.random.default_rng(0).lognormal(2, 1, 500)
    # launch/serve.py shape
    assert set(obs.latency_percentiles(lat)) == {"p50", "p95", "p99"}
    # benchmarks/perf_rangereach.py shape
    got = obs.latency_percentiles(lat, prefix="lat_p", suffix="_us")
    assert set(got) == {"lat_p50_us", "lat_p95_us", "lat_p99_us"}
    assert got["lat_p99_us"] == float(np.percentile(lat, 99))


def test_histogram_saturated_degrades_gracefully():
    rng = np.random.default_rng(5)
    sample = rng.lognormal(3.0, 0.5, 20000)
    h = Histogram(max_samples=128, sub=16)
    h.record_many(sample)
    assert h.saturated
    for p in (50, 95, 99):
        exact = float(np.percentile(sample, p))
        # bucket-interpolated: bounded relative error, not bit-for-bit
        assert abs(h.percentile(p) - exact) / exact < 0.10
    snap = h.snapshot()
    assert snap["count"] == 20000


def test_histogram_monotone_and_stats():
    h = Histogram.from_samples([1.0, 2.0, 3.0, 10.0])
    ps = [h.percentile(p) for p in (10, 50, 90, 99)]
    assert ps == sorted(ps)
    snap = h.snapshot()
    assert snap["min"] == 1.0 and snap["max"] == 10.0
    assert snap["count"] == 4


def test_histogram_merge_golden():
    """Merged percentiles are bit-for-bit np.percentile on the
    concatenated samples while the combined window is unsaturated."""
    rng = np.random.default_rng(9)
    a = rng.lognormal(3.0, 1.0, 700)
    b = rng.exponential(50.0, 300)
    ha = Histogram.from_samples(a, max_samples=2000)
    hb = Histogram.from_samples(b)
    assert ha.merge(hb) is ha
    both = np.concatenate([a, b])
    assert ha.count == 1000 and not ha.saturated
    for p in (0, 50, 95, 99, 100):
        assert ha.percentile(p) == float(np.percentile(both, p))
    snap = ha.snapshot()
    assert snap["min"] == both.min() and snap["max"] == both.max()
    assert snap["sum"] == pytest.approx(both.sum())
    with pytest.raises(ValueError, match="bucket layouts"):
        ha.merge(Histogram(sub=8))


def test_histogram_since_windowed_view():
    """state()/since() subtraction yields exact percentiles for just
    the values recorded in between (the time-series window)."""
    rng = np.random.default_rng(13)
    h = Histogram()
    first = rng.lognormal(2.0, 0.7, 400)
    h.record_many(first)
    st = h.state()
    second = rng.lognormal(4.0, 0.3, 300)
    h.record_many(second)
    win = h.since(st)
    assert win.count == 300 and win.sum == pytest.approx(second.sum())
    for p in (50, 95, 99):
        assert win.percentile(p) == float(np.percentile(second, p))
    assert win.min == second.min() and win.max == second.max()
    whole = h.since(None)
    assert whole.count == 700
    assert whole.percentile(50) == h.percentile(50)
    empty = h.since(h.state())              # no records in between
    assert empty.count == 0 and np.isnan(empty.percentile(50))


def test_histogram_count_above():
    h = Histogram.from_samples([1.0, 5.0, 10.0, 50.0, 100.0])
    assert h.count_above(10.0) == 3          # exact while unsaturated
    assert h.count_above(1000.0) == 0
    assert h.count_above(0.5) == 5


def test_counter_gauge_registry():
    reg = Registry()
    c = reg.counter("c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("c") is c          # get-or-create
    g = reg.gauge("g")
    g.set(7)
    g.set(3)
    assert g.value == 3 and g.max == 7    # high-water survives
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"]["max"] == 7
    reg.reset()
    assert c.value == 0 and g.max == 0


def test_counterdict_is_live_registry_view():
    reg = Registry()
    d = CounterDict("up.", ("a", "b"), registry=reg)
    d["a"] += 2                            # legacy increment style
    d["b"] = 9                             # legacy assignment style
    assert dict(d) == {"a": 2, "b": 9}     # legacy dict() snapshot
    assert reg.counter("up.a").value == 2  # same underlying counters
    reg.counter("up.b").inc()
    assert d["b"] == 10                    # registry writes visible


def test_upload_counters_absorbed():
    """The engine's legacy UPLOAD_COUNTERS global is a registry view."""
    from repro.core import engine as engine_mod

    before = dict(engine_mod.UPLOAD_COUNTERS)
    assert set(before) == {"host_uploads", "device_adoptions"}
    assert obs.REGISTRY.counter("engine.upload.host_uploads").value == \
        before["host_uploads"]


# ----------------------------------------------------------------- tracer

def test_span_disabled_records_nothing():
    t0 = len(obs.TRACER)
    with obs.span("x.y", cat="t", detail=1):
        pass
    assert len(obs.TRACER) == t0
    # disabled spans share one no-op object (the <2% overhead design)
    assert obs.span("a") is obs.span("b")


def test_span_enabled_records_chrome_events():
    obs.enable()
    with obs.span("layer.stage", cat="test", n=3):
        time.sleep(0.002)
    obs.disable()
    trace = obs.TRACER.chrome_trace()
    ev = [e for e in trace["traceEvents"] if e["name"] == "layer.stage"]
    assert len(ev) == 1
    e = ev[0]
    assert e["ph"] == "X" and e["cat"] == "test"
    assert e["dur"] >= 2e3                # microseconds
    assert e["args"] == {"n": 3}
    assert {"ts", "pid", "tid"} <= set(e)
    json.dumps(trace)                      # serialisable as-is


def test_traced_decorator():
    calls = []

    @obs.traced("deco.fn", cat="t")
    def fn(x):
        calls.append(x)
        return x * 2

    assert fn(2) == 4                      # disabled: passthrough
    obs.enable()
    assert fn(3) == 6
    obs.disable()
    assert calls == [2, 3]
    assert obs.stage_totals("deco.")["deco.fn"] >= 0.0


def test_tracer_thread_safety_and_bound():
    tr = Tracer(max_events=5000)
    tr.start()

    def work():
        for i in range(1000):
            tr.record("t.span", "", 0, 10, None)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr) == 5000                 # bounded, never over
    assert tr.dropped == 3000              # the rest counted, not lost
    assert tr.summary()["t.span"]["count"] == 5000


def test_stage_totals_and_summary():
    obs.enable()
    for _ in range(3):
        with obs.span("eng.a"):
            pass
    with obs.span("eng.b"):
        pass
    with obs.span("other.c"):
        pass
    obs.disable()
    totals = obs.stage_totals("eng.")
    assert set(totals) == {"eng.a", "eng.b"}
    s = obs.TRACER.summary()
    assert s["eng.a"]["count"] == 3
    assert s["eng.a"]["mean_us"] == pytest.approx(
        s["eng.a"]["total_us"] / 3)


def test_coverage_interval_union():
    tr = Tracer()
    base = 1_000_000_000  # 1s in ns
    # two overlapping spans + one disjoint: union = [0.1, 0.3] + [0.5, 0.6]
    tr.record("l.a", "", int(0.1 * base), int(0.15 * base), None)
    tr.record("l.b", "", int(0.2 * base), int(0.10 * base), None)
    tr.record("l.c", "", int(0.5 * base), int(0.10 * base), None)
    tr.record("zz.d", "", int(0.7 * base), int(0.10 * base), None)
    cov = tr.coverage(0.0, 1.0, prefixes=("l.",))
    assert cov == pytest.approx(0.30, abs=1e-6)
    assert tr.coverage(0.0, 1.0) == pytest.approx(0.40, abs=1e-6)


# -------------------------------------------------------------- query log

def test_rect_bucket():
    assert rect_bucket([0, 0, 1, 1]) == 0
    assert rect_bucket([0, 0, 2, 2]) == 2          # area 4 -> log2 = 2
    assert rect_bucket([0, 0, 0, 5]) == -64        # degenerate
    assert rect_bucket([0, 0, 1e30, 1e30]) == 63   # clamped
    assert rect_bucket([0, 0, 1e-30, 1e-30]) == -63


def test_querylog_bounded_with_aggregates():
    log = QueryLog(capacity=8)
    for i in range(20):
        log.record("reach", "user", 0, i % 3, 1e-3, i)
    assert len(log) == 8
    assert log.total == 20
    assert log.dropped == 12
    snap = log.snapshot()
    assert snap["by_class"]["reach"] == 20         # eviction-proof
    assert sum(snap["by_shard"].values()) == 20
    assert snap["latency_us"]["p50"] == pytest.approx(1000.0)


def test_querylog_jsonl_roundtrip(tmp_path):
    log = QueryLog(capacity=16)
    log.record_batch(
        "reach", ["user", "sink"],
        np.array([[0, 0, 1, 1], [0, 0, 2, 2]], dtype=np.float32),
        np.array([0, 1]), [1e-3, 2e-3], [1, 0])
    path = log.to_jsonl(str(tmp_path / "q.jsonl"))
    lines = [json.loads(l) for l in open(path)]
    header, recs = lines[0], lines[1:]
    assert header == {"schema_version": SCHEMA_VERSION,
                      "fields": list(FIELDS)}
    assert len(recs) == 2
    assert all(set(r) == set(FIELDS) for r in recs)
    assert recs[0]["vertex_class"] == "user"
    assert recs[1]["rect_bucket"] == 2
    assert recs[1]["shard"] == 1
    # schema-v2 defaults when the producer reports nothing
    assert recs[0]["status"] == "ok" and recs[0]["retries"] == 0
    assert recs[0]["u"] == -1


def test_querylog_status_and_sinks():
    """v2 fields flow through record/record_batch; streaming sinks see
    every record before ring eviction."""
    log = QueryLog(capacity=4)
    seen = []
    log.add_sink(seen.append)
    log.record_batch(
        "reach", ["user"] * 3,
        np.zeros((3, 4), dtype=np.float32), np.zeros(3),
        [1e-3] * 3, [0] * 3, us=np.array([7, 7, 9]),
        statuses=["ok", "degraded", "ok"], retries=2)
    for i in range(6):                       # overflow the ring
        log.record("reach", "user", 0, 0, 1e-3, 0, u=7)
    assert len(log) == 4 and log.dropped == 5
    assert len(seen) == 9                    # sinks saw the whole stream
    snap = log.snapshot()
    assert snap["schema_version"] == SCHEMA_VERSION
    assert snap["by_status"] == {"ok": 8, "degraded": 1}


# ------------------------------------------------- engine + frontend obs

def test_engine_batch_metrics_gated(built):
    _, _, eng, us, rects = built
    eng.query_batch(us, rects)             # disabled: no recording
    h = obs.REGISTRY.histogram("engine.batch_us")
    c0 = h.snapshot()["count"]
    obs.enable()
    eng.query_batch(us, rects)
    obs.disable()
    assert h.snapshot()["count"] == c0 + 1
    assert obs.REGISTRY.counter("engine.reach.queries").value >= len(us)
    assert obs.REGISTRY.gauge("engine.n_compiles").value == eng.n_compiles
    eng.query_batch(us, rects)             # disabled again: flat
    assert h.snapshot()["count"] == c0 + 1


def test_mixed_serve_coverage_at_least_95pct(built):
    """The acceptance gate: spans across serve/engine/frontend layers
    cover >=95% of a mixed serve's wall time."""
    from repro.cluster import Frontend

    _, _, eng, us, rects = built
    obs.enable()
    t0 = time.perf_counter()
    with obs.span("serve.mixed_pass", cat="serve"):
        eng.query_batch(us, rects)                   # direct engine
        with Frontend(eng, max_batch=32, max_delay=1e-3) as fe:
            fe.submit_many(us[:64], rects[:64])      # micro-batched
    t1 = time.perf_counter()
    obs.disable()
    cov = obs.coverage(t0, t1)
    assert cov >= 0.95, f"span coverage {cov:.3f} < 0.95"
    totals = obs.stage_totals()
    layers = {name.split(".")[0] for name in totals}
    assert {"serve", "engine", "frontend"} <= layers
    snap = obs.snapshot()
    assert snap["schema_version"] == 2
    assert snap["query_log"]["total"] >= 64          # frontend logged
    assert "frontend.flush" in snap["spans"]


def test_frontend_explicit_query_log(built):
    """An explicit query_log records even with obs disabled; shard and
    vertex-class fields are populated."""
    from repro.cluster import Frontend

    _, idx, eng, us, rects = built
    qlog = QueryLog(capacity=256)
    with Frontend(eng, max_batch=16, max_delay=1e-3,
                  query_log=qlog) as fe:
        fe.submit_many(us[:48], rects[:48])
    assert qlog.total == 48
    recs = qlog.records()
    classes = {r[I_VERTEX_CLASS] for r in recs}
    assert classes <= {"user", "sink", "unknown"}
    excluded = np.asarray(idx.excluded)
    want_sink = int(excluded[us[:48].astype(np.int64)].sum())
    assert sum(1 for r in recs
               if r[I_VERTEX_CLASS] == "sink") == want_sink


def test_obs_dump_writes_artifacts(tmp_path, built):
    _, _, eng, us, rects = built
    obs.enable()
    eng.query_batch(us, rects)
    obs.disable()
    paths = obs.dump(str(tmp_path))
    trace = json.load(open(paths["trace"]))
    assert any(e["name"] == "engine.query_batch"
               for e in trace["traceEvents"])
    snap = json.load(open(paths["metrics"]))
    assert "engine.batch_us" in snap["metrics"]["histograms"]
    qlines = open(paths["querylog"]).read().splitlines()
    assert len(qlines) == 1                  # header only: nothing served
    assert json.loads(qlines[0])["schema_version"] == SCHEMA_VERSION
    prom = open(paths["prom"]).read()        # OpenMetrics always written
    assert prom.endswith("# EOF\n")
    assert "repro_engine_batch_us_count 1" in prom


def test_engine_cost_model_sanity(built):
    _, _, eng, us, rects = built
    eng.query_batch(us, rects)
    cm = obs.engine_cost_model(eng)
    assert cm["batches"] >= 1
    assert 0 < cm["candidate_tiles_per_batch"] <= \
        cm["full_scan_tiles_per_batch"]
    assert 0 < cm["scan_fraction"] <= 1.0
    assert cm["scan_bytes_per_batch"] > 0
    assert cm["prune_bytes_per_batch"] > 0
    assert cm["tile_shape"]["planes"] == 4


def test_engine_cost_model_reads_the_pyramid_once_per_call(built):
    """The pyramid is read once per kernel call, whatever the batch
    size: 16 bytes a fine tile (4 float32 rows) and 16 a coarse group
    of 8, so 18 bytes a tile."""
    from repro.core import QueryEngine

    _, idx, _, us, rects = built
    eng = QueryEngine(idx)
    eng.query_batch(us, rects)
    eng.query_batch(us[:8], rects[:8])
    calls = 1 + eng.stats["fused_reruns"] / eng.stats["batches"]
    cm = obs.engine_cost_model(eng)
    assert cm["prune_bytes_per_batch"] == pytest.approx(
        18 * eng.n_tiles * calls)
    for _ in range(4):
        eng.query_batch(us[:8], rects[:8])
    calls = 1 + eng.stats["fused_reruns"] / eng.stats["batches"]
    assert obs.engine_cost_model(eng)["prune_bytes_per_batch"] == \
        pytest.approx(18 * eng.n_tiles * calls)


def test_enabled_spans_land_in_a_profiler_capture(tmp_path):
    """An enabled span is also a ``jax.profiler`` annotation on a host
    plane of the capture, on the profiler's clock; a disabled one is
    not."""
    import jax
    from jax.profiler import ProfileData

    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("test.enabled_span", cat="t"):
            time.sleep(0.001)
        obs.disable()
        with obs.span("test.disabled_span", cat="t"):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    (path,) = list(tmp_path.glob("**/*.xplane.pb"))
    names = {e.name: e.duration_ns for plane in
             ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert names["test.enabled_span"] >= 1e6
    assert "test.disabled_span" not in names
    assert [e[0] for e in obs.TRACER.events()] == ["test.enabled_span"]


def test_sharded_engine_spans_nest(built):
    """``cluster.dispatch`` and ``cluster.block`` lie inside
    ``cluster.fused``; ``cluster.sync`` then ``cluster.tally`` follow it
    inside ``cluster.query_batch``.  Tracing writes no ``cluster.*``
    registry entries: the spans and the engine's own counters hold
    that."""
    from repro.cluster import ShardedEngine

    _, idx, _, us, rects = built
    eng = ShardedEngine(idx, n_shards=1)
    eng.query_batch(us, rects)
    routed = int(eng.shard_queries.sum())
    obs.enable()
    eng.query_batch(us, rects)
    obs.disable()
    by = {}
    for e in obs.TRACER.events():
        by.setdefault(e[0], []).append(e)

    def inside(inner, outer):
        return outer[3] <= inner[3] and \
            inner[3] + inner[4] <= outer[3] + outer[4]

    (qb,), (fused,), (sync,), (tally,) = (
        by[f"cluster.{n}"] for n in ("query_batch", "fused", "sync",
                                     "tally"))
    assert by["cluster.dispatch"] and by["cluster.block"]
    for e in by["cluster.dispatch"] + by["cluster.block"]:
        assert inside(e, fused)
    assert by["cluster.dispatch"][0][3] + by["cluster.dispatch"][0][4] \
        <= by["cluster.block"][0][3]
    assert fused[3] + fused[4] <= sync[3]
    assert sync[3] + sync[4] <= tally[3]
    for e in (fused, sync, tally):
        assert inside(e, qb)
    snap = obs.REGISTRY.snapshot()
    assert not [k for kind in snap.values() if isinstance(kind, dict)
                for k in kind if k.startswith("cluster.")]
    assert int(eng.shard_queries.sum()) == 2 * routed > 0


def test_device_trace_degrades_gracefully(tmp_path):
    # a requested capture runs; one that cannot start (here a second
    # concurrent capture) raises instead of passing untraced; disabled
    # is a no-op
    with obs.device_trace(str(tmp_path / "prof"), enabled=True):
        with pytest.raises(RuntimeError):
            with obs.device_trace(str(tmp_path / "prof2"), enabled=True):
                pass
    with obs.device_trace("", enabled=False):
        pass


# -------------------------------------------- host-fallback (satellite)

def test_host_fallback_warns_once_per_reason_and_counts():
    import repro.core.api as api_mod
    from repro.core.api import batch_query, build_dynamic_index, build_index

    rng = np.random.default_rng(11)
    g = random_geosocial(rng, 120, 360)
    us, rects = random_queries(rng, g, 4)
    geo = build_index(g, "georeach")                   # no device engine
    dyn = build_dynamic_index(g, "2dreach-comp")       # host-engine wrapper
    assert getattr(dyn, "engine", None) == "host"

    api_mod._FALLBACK_WARNED.discard(
        ("unsupported-index", "GeoReachIndex"))
    api_mod._FALLBACK_WARNED.discard(
        ("wrapper-host-engine", "DynamicIndex"))
    c_unsup = obs.REGISTRY.counter("api.host_fallback.unsupported-index")
    c_wrap = obs.REGISTRY.counter("api.host_fallback.wrapper-host-engine")
    n_unsup, n_wrap = c_unsup.value, c_wrap.value

    # distinct causes each get their own (single) warning
    with pytest.warns(RuntimeWarning, match="unsupported-index"):
        batch_query(geo, us, rects, engine="device")
    with pytest.warns(RuntimeWarning, match="wrapper-host-engine"):
        batch_query(dyn, us, rects, engine="device")
    # second occurrence of each: silent, but still counted
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        batch_query(geo, us, rects, engine="device")
        batch_query(dyn, us, rects, engine="device")
    assert c_unsup.value == n_unsup + 2
    assert c_wrap.value == n_wrap + 2
