"""The readers of the program's request-path spans: device idle time that
no span covers, on events made by hand; the span readers on synthetic
runs, and on a program without those spans; and a traced run of each
cell on the CPU, whose profiler trace holds the program's spans."""

import gc
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from bench import idle_spans, spec, trace_reduce  # noqa: E402
from bench import run as bench_run  # noqa: E402

from test_bench_harness import CELLS, tiny_cell  # noqa: E402

OPS = "XLA Ops"


def op(name, start, dur):
    return (OPS, name, float(start), float(dur))


def host(name, start, dur):
    return ("thread", name, float(start), float(dur))


def test_idle_unspanned_intersects_intervals():
    # device busy [10, 20] and [60, 70] of the window [0, 100]: gaps
    # [0, 10], [20, 60] and [70, 100]
    trace = {"device": {"/device:TPU:0": [op("fused_serve_pallas.1", 10, 10),
                                          op("copy", 60, 10)]},
             "host": {
                 # two threads whose spans overlap: the union counts
                 "/host:CPU": [host("frontend.flush", 0, 5),
                               host("cluster.sync", 20, 10),
                               host("bench.window", 0, 100),
                               host("PjitFunction", 85, 15)],
                 "/host:CPU:1": [host("frontend.resolve", 25, 20),
                                 host("frontend.wait", 70, 18)]}}
    idle_s, window_s = idle_spans.idle_unspanned(trace, window=(0.0, 100.0))
    assert window_s == pytest.approx(100e-9)
    # uncovered: [5, 10], [45, 60] and [88, 100]; the harness's own
    # annotation and JAX's are no program spans
    assert idle_s == pytest.approx(32e-9)
    # each gap's midpoint lies in a span, so the midpoint breakdown
    # charges every gap whole to one and leaves nothing unnamed
    spans = [(n, s, s + d) for evs in trace["host"].values()
             for _l, n, s, d in evs if n.startswith(idle_spans.PREFIXES)]
    gaps = dict(trace_reduce.reduce(trace, spans, window=(0.0, 100.0))
                ["idle_gaps"])
    assert gaps == {"frontend.flush": pytest.approx(10e-9),
                    "frontend.resolve": pytest.approx(40e-9),
                    "frontend.wait": pytest.approx(30e-9)}


def test_idle_unspanned_midpoint_in_a_span_tail_outside():
    trace = {"device": {"/device:TPU:0": [op("k", 0, 10), op("k", 50, 10)]},
             "host": {"/host:CPU": [host("cluster.sync", 10, 30)]}}
    # the gap [10, 50]: midpoint 30 inside cluster.sync, tail [40, 50]
    # outside every span
    spans = [("cluster.sync", 10.0, 40.0)]
    gaps = dict(trace_reduce.reduce(trace, spans, window=(0.0, 60.0))
                ["idle_gaps"])
    assert gaps == {"cluster.sync": pytest.approx(40e-9)}
    idle_s, _ = idle_spans.idle_unspanned(trace, window=(0.0, 60.0))
    assert idle_s == pytest.approx(10e-9)


def test_idle_unspanned_averages_devices_and_needs_spans():
    dev = {"/device:TPU:0": [op("k", 0, 50)],
           "/device:TPU:1": [op("k", 0, 80)],
           "/device:TPU:2": [op("k", 200, 5)]}     # outside the window
    trace = {"device": dev,
             "host": {"/host:CPU": [host("frontend.flush", 90, 10)]}}
    idle_s, _ = idle_spans.idle_unspanned(trace, window=(0.0, 100.0))
    assert idle_s == pytest.approx((40e-9 + 10e-9) / 2)
    # a program that puts no span into the trace gives nothing to read
    bare = {"device": dev, "host": {"/host:CPU": [host("bench.window",
                                                       0, 100)]}}
    assert idle_spans.idle_unspanned(bare, window=(0.0, 100.0)) is None
    run = fake_run(trace=dict(bare, host={"/host:CPU": [
        host(trace_reduce.WINDOW, 0, 100)]}))
    assert spec.reader("device.idle_unspanned_pct.open")(run) is None
    run.trace["host"]["/host:CPU"].append(host("cluster.fused", 0, 90))
    # both devices idle and unspanned in [90, 100] only
    assert spec.reader("device.idle_unspanned_pct.open")(run) == \
        pytest.approx(10.0)


def fake_run(spans=(), trace=None):
    """A ``Run`` holding only program spans and a trace."""
    run = object.__new__(bench_run.Run)
    run.spans = list(spans)
    run.trace = trace
    run._reduced = None
    return run


def span(name, t0, dur, **args):
    return (name, "", 1, t0, dur, args or None)


SPANS = [
    span("frontend.flush", 0, 5_000_000, n=2, wait_us_sum=3000.0,
         late_us=0.0),
    span("cluster.query_batch", 1_000_000, 4_000_000, n=2),
    span("cluster.dispatch", 2_000_000, 250_000),
    span("cluster.dispatch", 2_300_000, 150_000),   # a capacity re-run
    span("frontend.resolve", 5_000_000, 1_500_000, n=2),
    span("frontend.flush", 9_000_000, 5_000_000, n=6, wait_us_sum=9000.0,
         late_us=120.0),
    span("cluster.query_batch", 10_000_000, 4_000_000, n=6),
    span("cluster.dispatch", 11_000_000, 200_000),
    span("frontend.resolve", 14_000_000, 500_000, n=6),
]


@pytest.mark.parametrize("suffix", [".open", ".closed"])
def test_span_readers_on_a_synthetic_run(suffix):
    run = fake_run(SPANS)
    read = {m: spec.reader(m + suffix) for m in (
        "frontend.queue_wait_ms", "frontend.resolve_ms_per_batch",
        "engine.dispatch_ms_per_batch")}
    # (3,000 + 9,000) us over 8 requests
    assert read["frontend.queue_wait_ms"](run) == pytest.approx(1.5)
    # (1.5 + 0.5) ms over 2 batches
    assert read["frontend.resolve_ms_per_batch"](run) == pytest.approx(1.0)
    # (0.25 + 0.15 + 0.2) ms over 2 batches
    assert read["engine.dispatch_ms_per_batch"](run) == pytest.approx(0.3)


def test_span_readers_find_nothing_without_the_spans():
    """A program that lacks the spans and args gives no reading, and
    raises nothing."""
    older = [span("frontend.flush", 0, 5, n=2, reason="n_flush_full"),
             span("cluster.query_batch", 1, 4, n=2),
             span("cluster.fused", 2, 2, batch=2)]
    for m in ("frontend.queue_wait_ms", "frontend.resolve_ms_per_batch",
              "engine.dispatch_ms_per_batch",
              "device.idle_unspanned_pct"):
        read = spec.reader(m + ".open")
        assert read(fake_run(older)) is None
        assert read(fake_run()) is None


@pytest.mark.parametrize("name", CELLS)
def test_traced_cpu_run_puts_program_spans_into_the_trace(name):
    from repro import obs

    cell = tiny_cell(name)
    served = bench_run.Served(cell, 2**31 + 23, on_chip=False)
    try:
        served.warm(cell["traffic"], 2**31 + 23)
        run = bench_run.measure(served, cell["traffic"], 1.0, 2**31 + 23,
                                trace=True)
    finally:
        served.close()
        gc.unfreeze()
        obs.disable()
    try:
        names = {n for evs in run.trace["host"].values()
                 for _l, n, _s, _d in evs}
        for s in ("frontend.flush", "frontend.assemble",
                  "frontend.resolve", "cluster.query_batch",
                  "cluster.dispatch", "cluster.block", "cluster.sync",
                  "cluster.tally"):
            assert s in names, s
        # a closed loop keeps a full batch queued: the scheduler never
        # waits
        if cell["traffic"]["loop"] == "open":
            assert "frontend.wait" in names
        assert idle_spans.host_spans(run.trace)
        got = bench_run.read_metrics(run, cell["per_layer"])
        suffix = name.split(".")[1].split("-")[0]
        for m in ("frontend.queue_wait_ms", "frontend.resolve_ms_per_batch",
                  "engine.dispatch_ms_per_batch"):
            assert got[f"{m}.{suffix}"]["value"] > 0
        # the CPU has no device plane: no device reading
        assert f"device.idle_unspanned_pct.{suffix}" not in got
    finally:
        obs.reset()
