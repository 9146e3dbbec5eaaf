"""Frontend under load: deadline misses, backpressure, gauge truth.

These tests drive the micro-batching frontend's scheduler with an
injectable fake clock and a blockable engine, so flush deadlines, queue
saturation and lateness accounting are exercised *deterministically* —
no wall-clock sleeps gate the assertions; real time is only ever spent
waiting on state transitions that are already guaranteed to happen.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.cluster import Frontend
from repro.obs import QueryLog
from repro.obs.metrics import Registry
from repro.resilience import (
    DeadlineExceeded,
    FrontendClosed,
    Overloaded,
    QueueFull,
)

RECT = np.array([0.0, 0.0, 1.0, 1.0], dtype=np.float32)


class FakeClock:
    """Injectable monotonic clock; ``advance`` also wakes the scheduler
    so its deadline wait re-evaluates against the new time."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, fe: Frontend, dt: float) -> None:
        self.t += dt
        with fe._cond:
            fe._cond.notify_all()


class BlockableEngine:
    """Answers True for everything; optionally blocks inside the first
    ``query_batch`` until released (holds the frontend inflight)."""

    def __init__(self, block_first: bool = False):
        self.calls: list = []
        self.entered = threading.Event()   # set when a serve starts
        self.release = threading.Event()   # opens the blocked serve
        self._block_first = block_first

    def query_batch(self, us, rects):
        self.calls.append(np.asarray(us).copy())
        self.entered.set()
        if self._block_first and len(self.calls) == 1:
            assert self.release.wait(timeout=30), "engine never released"
        return np.ones(len(np.asarray(us)), dtype=bool)


def _await(predicate, timeout=10.0, what="condition"):
    """Bounded wait for a cross-thread state transition."""
    deadline = time.monotonic() + timeout
    while not predicate():
        assert time.monotonic() < deadline, f"timed out awaiting {what}"
        time.sleep(0.001)


def test_deadline_flush_on_time_is_not_a_miss():
    clock = FakeClock()
    reg = Registry()
    eng = BlockableEngine()
    with Frontend(eng, max_batch=8, max_delay=10.0, metrics=reg,
                  clock=clock) as fe:
        fut = fe.submit(0, RECT)
        # under max_batch pending and before the deadline: no flush
        assert not fut.done()
        clock.advance(fe, 10.0)            # exactly the deadline
        assert fut.result(timeout=10) is True
        assert fe.stats["n_flush_deadline"] == 1
        assert fe.stats["n_deadline_misses"] == 0
        assert reg.counter("frontend.n_flush_deadline").value == 1
        assert reg.counter("frontend.deadline_misses").value == 0
        h = reg.histogram("frontend.flush_lateness_us")
        assert h.snapshot()["count"] == 1
        assert h.snapshot()["max"] == 0.0  # flushed exactly on time


def test_deadline_miss_behind_inflight_batch():
    """A batch whose flush starts after deadline + grace (because the
    scheduler was stuck behind an inflight batch) counts as a miss, and
    the lateness histogram records how far past the SLO it started."""
    clock = FakeClock()
    reg = Registry()
    eng = BlockableEngine(block_first=True)
    fe = Frontend(eng, max_batch=1, max_queue=8, max_delay=10.0,
                  deadline_grace=5.0, metrics=reg, clock=clock)
    try:
        f1 = fe.submit(0, RECT)            # flushes full, engine blocks
        assert eng.entered.wait(timeout=10)
        f2 = fe.submit(1, RECT)            # stuck behind the inflight
        clock.advance(fe, 100.0)           # blow way past f2's deadline
        eng.release.set()
        assert f1.result(timeout=10) is True
        assert f2.result(timeout=10) is True
        assert fe.stats["n_deadline_misses"] == 1
        assert reg.counter("frontend.deadline_misses").value == 1
        h = reg.histogram("frontend.flush_lateness_us")
        # f2 started (100 - 10) fake seconds late
        assert h.snapshot()["max"] == pytest.approx(90e6)
    finally:
        fe.close()


def test_lateness_within_grace_is_not_a_miss():
    clock = FakeClock()
    reg = Registry()
    eng = BlockableEngine()
    with Frontend(eng, max_batch=8, max_delay=10.0, deadline_grace=5.0,
                  metrics=reg, clock=clock) as fe:
        fut = fe.submit(0, RECT)
        clock.advance(fe, 13.0)            # 3s late, inside 5s grace
        assert fut.result(timeout=10) is True
        assert fe.stats["n_deadline_misses"] == 0
        h = reg.histogram("frontend.flush_lateness_us")
        assert h.snapshot()["max"] == pytest.approx(3e6)


def test_queue_full_backpressure_blocks_and_recovers():
    clock = FakeClock()
    reg = Registry()
    eng = BlockableEngine(block_first=True)
    fe = Frontend(eng, max_batch=2, max_queue=2, max_delay=10.0,
                  metrics=reg, clock=clock)
    try:
        fa = fe.submit(0, RECT)
        fb = fe.submit(1, RECT)            # full flush; engine blocks
        assert eng.entered.wait(timeout=10)
        fc = fe.submit(2, RECT)            # queue 1/2
        fd = fe.submit(3, RECT)            # queue 2/2 — at capacity
        extra = {}

        def blocked_submit():
            extra["fut"] = fe.submit(4, RECT)

        th = threading.Thread(target=blocked_submit)
        th.start()
        # the 5th submit must block (counted before it waits) ...
        _await(lambda: fe.stats["n_submit_blocked"] == 1,
               what="submit to block on the full queue")
        assert th.is_alive()
        assert reg.counter("frontend.submit_blocked").value == 1
        # ... until the inflight batch completes and frees queue space
        eng.release.set()
        th.join(timeout=10)
        assert not th.is_alive()
        # the straggler sits alone under max_batch: only its deadline
        # (in fake time) can flush it
        clock.advance(fe, 50.0)
        for f in (fa, fb, fc, fd, extra["fut"]):
            assert f.result(timeout=10) is True
        assert fe.stats["n_requests"] == 5
        served = sum(len(c) for c in eng.calls)
        assert served == 5
    finally:
        fe.close()


def test_gauges_track_depth_occupancy_inflight():
    clock = FakeClock()
    reg = Registry()
    eng = BlockableEngine(block_first=True)
    fe = Frontend(eng, max_batch=4, max_queue=16, max_delay=10.0,
                  metrics=reg, clock=clock)
    try:
        for i in range(4):
            fe.submit(i, RECT)             # full flush; engine blocks
        assert eng.entered.wait(timeout=10)
        assert reg.gauge("frontend.inflight").value == 1
        for i in range(3):
            fe.submit(4 + i, RECT)         # pile up behind the inflight
        assert reg.gauge("frontend.queue_depth").max >= 3
        eng.release.set()
        clock.advance(fe, 10.0)            # deadline flush for the 3
        _await(lambda: fe.stats["n_batches"] == 2, what="both flushes")
        assert reg.gauge("frontend.inflight").value == 0
        assert reg.gauge("frontend.batch_occupancy").max == 1.0   # 4/4
        assert reg.gauge("frontend.batch_occupancy").value == \
            pytest.approx(3 / 4)                                  # 3/4
        h = reg.histogram("frontend.batch_size")
        assert h.snapshot()["count"] == 2
        assert h.snapshot()["max"] == 4.0
        assert reg.counter("frontend.requests").value == 7
        # queue-wait histogram saw one entry per request, in fake time
        assert reg.histogram(
            "frontend.queue_wait_us").snapshot()["count"] == 7
    finally:
        fe.close()


def test_fake_clock_does_not_leak_into_default_frontend():
    """Without an injected clock the frontend uses time.monotonic and
    still serves (guard against the clock plumbing regressing the real
    path)."""
    eng = BlockableEngine()
    with Frontend(eng, max_batch=4, max_delay=1e-3) as fe:
        got = fe.submit_many(np.arange(4), np.tile(RECT, (4, 1)))
    assert got.all()
    assert fe.stats["n_batches"] >= 1


# ----------------------------------------------------------------------
# typed errors: QueueFull, Overloaded, DeadlineExceeded, FrontendClosed
# ----------------------------------------------------------------------


def test_submit_timeout_raises_queue_full():
    reg = Registry()
    eng = BlockableEngine(block_first=True)
    fe = Frontend(eng, max_batch=2, max_queue=2, max_delay=10.0,
                  metrics=reg)
    try:
        fe.submit(0, RECT)
        fe.submit(1, RECT)                 # full flush; engine blocks
        assert eng.entered.wait(timeout=10)
        fe.submit(2, RECT)
        fe.submit(3, RECT)                 # queue at capacity
        with pytest.raises(QueueFull):
            fe.submit(4, RECT, timeout=0.05)
        assert fe.stats["n_queue_full_timeouts"] == 1
        assert reg.counter("frontend.queue_full_timeouts").value == 1
        # a QueueFull submit left no residue: capacity frees, serving
        # continues, and the shed request was simply never enqueued
        eng.release.set()
        assert fe.stats["n_requests"] == 4
    finally:
        fe.close()


def test_overloaded_shed_on_doomed_deadline():
    """A request whose budget cannot survive even the flush delay is
    shed with Overloaded instead of queued to die."""
    reg = Registry()
    eng = BlockableEngine()
    fe = Frontend(eng, max_batch=8, max_delay=0.5, max_queue=16,
                  metrics=reg, slo=0.01)
    try:
        with pytest.raises(Overloaded):
            fe.submit(0, RECT)             # default slo 10ms < 500ms
        # an explicit generous deadline overrides the doomed default
        fut = fe.submit(1, RECT, deadline=60.0)
        fe.flush(timeout=10)
        assert fut.result(timeout=10) is True
        assert fe.stats["n_shed"] == 1
        assert reg.counter("frontend.shed").value == 1
    finally:
        fe.close()


def test_deadline_expired_in_queue_is_dropped_typed():
    clock = FakeClock()
    reg = Registry()
    eng = BlockableEngine(block_first=True)
    fe = Frontend(eng, max_batch=1, max_queue=8, max_delay=0.1,
                  metrics=reg, clock=clock)
    try:
        fa = fe.submit(0, RECT)            # flushes alone; blocks engine
        assert eng.entered.wait(timeout=10)
        fb = fe.submit(1, RECT, deadline=0.5)
        fc = fe.submit(2, RECT, deadline=50.0)
        clock.advance(fe, 1.0)             # fb's budget expires queued
        eng.release.set()
        assert fa.result(timeout=10) is True
        with pytest.raises(DeadlineExceeded):
            fb.result(timeout=10)
        assert fc.result(timeout=10) is True
        assert fe.stats["n_deadline_dropped"] == 1
        assert reg.counter("frontend.deadline_dropped").value == 1
        # the dropped request never reached the engine
        assert sum(len(c) for c in eng.calls) == 2
    finally:
        fe.close()


def test_engine_exception_latches_and_scheduler_survives():
    """An engine blow-up resolves exactly the affected batch's futures
    with the error; the scheduler thread survives and keeps serving."""

    class Exploding:
        def __init__(self):
            self.calls = 0

        def query_batch(self, us, rects):
            self.calls += 1
            if self.calls == 1:
                raise ValueError("device on fire")
            return np.ones(len(np.asarray(us)), dtype=bool)

    eng = Exploding()
    with Frontend(eng, max_batch=2, max_delay=10.0) as fe:
        fa = fe.submit(0, RECT)
        fb = fe.submit(1, RECT)            # full flush -> boom
        for f in (fa, fb):
            with pytest.raises(ValueError):
                f.result(timeout=10)
        # same frontend, next batch: served fine by the live scheduler
        fc = fe.submit(2, RECT)
        fd = fe.submit(3, RECT)
        assert fc.result(timeout=10) is True
        assert fd.result(timeout=10) is True
    assert eng.calls == 2


def test_close_drain_false_fails_pending_typed():
    eng = BlockableEngine(block_first=True)
    fe = Frontend(eng, max_batch=2, max_queue=8, max_delay=10.0)
    fa = fe.submit(0, RECT)
    fb = fe.submit(1, RECT)                # full flush; engine blocks
    assert eng.entered.wait(timeout=10)
    fc = fe.submit(2, RECT)                # still queued
    eng.release.set()
    fe.close(timeout=10, drain=False)
    # the inflight batch finished; the queued request failed typed
    assert fa.result(timeout=10) is True
    assert fb.result(timeout=10) is True
    with pytest.raises(FrontendClosed):
        fc.result(timeout=10)
    with pytest.raises(FrontendClosed):
        fe.submit(3, RECT)
    # FrontendClosed subclasses RuntimeError: pre-existing callers that
    # caught the old error keep working
    with pytest.raises(RuntimeError):
        fe.submit(4, RECT)


def test_close_drain_true_still_serves_everything():
    eng = BlockableEngine()
    fe = Frontend(eng, max_batch=64, max_delay=10.0)
    futs = [fe.submit(i, RECT) for i in range(5)]
    fe.close(timeout=10)                   # drain=True default
    assert all(f.result(timeout=10) is True for f in futs)


# ----------------------------------------------------------------------
# spans of the request path
# ----------------------------------------------------------------------

@pytest.fixture
def traced():
    """The global tracer on and empty for one test."""
    obs.disable()
    obs.reset()
    obs.enable()
    try:
        yield obs.TRACER
    finally:
        obs.disable()
        obs.reset()


def _inside(inner, outer):
    return outer[3] <= inner[3] and \
        inner[3] + inner[4] <= outer[3] + outer[4]


def test_flush_span_carries_wait_sum_and_lateness(traced):
    """``frontend.flush`` carries the sum over its batch of (flush start
    - enqueue) and the flush's lateness, on the Frontend's own clock."""
    clock = FakeClock()
    eng = BlockableEngine()
    with Frontend(eng, max_batch=8, max_delay=10.0, clock=clock,
                  query_log=QueryLog()) as fe:
        futs = [fe.submit(0, RECT)]
        clock.advance(fe, 1.0)
        futs.append(fe.submit(1, RECT))
        clock.advance(fe, 1.0)
        futs.append(fe.submit(2, RECT))
        clock.advance(fe, 8.0)             # the oldest's deadline: t=10
        assert all(f.result(timeout=10) for f in futs)
    (flush,) = [e for e in traced.events() if e[0] == "frontend.flush"]
    args = flush[5]
    assert args["n"] == 3 and args["reason"] == "n_flush_deadline"
    assert args["wait_us_sum"] == pytest.approx((10 + 9 + 8) * 1e6)
    assert args["late_us"] == 0.0


def test_late_flush_reports_its_lateness(traced):
    clock = FakeClock()
    eng = BlockableEngine(block_first=True)
    fe = Frontend(eng, max_batch=1, max_queue=8, max_delay=10.0,
                  clock=clock)
    try:
        f1 = fe.submit(0, RECT)            # flushes full, engine blocks
        assert eng.entered.wait(timeout=10)
        f2 = fe.submit(1, RECT)            # waits behind the inflight
        clock.advance(fe, 100.0)
        eng.release.set()
        assert f1.result(timeout=10) and f2.result(timeout=10)
    finally:
        fe.close()
    flushes = sorted((e for e in traced.events()
                      if e[0] == "frontend.flush"), key=lambda e: e[3])
    assert [e[5]["late_us"] for e in flushes] == \
        [0.0, pytest.approx(90e6)]
    assert flushes[1][5]["wait_us_sum"] == pytest.approx(100e6)


def test_request_path_spans_nest_on_the_scheduler_thread(traced):
    """``frontend.wait`` around the scheduler's waits, ``assemble``
    inside ``flush``, then ``resolve`` and ``querylog`` after it, all on
    the scheduler's thread."""
    clock = FakeClock()
    eng = BlockableEngine()
    qlog = QueryLog()
    fe = Frontend(eng, max_batch=4, max_delay=10.0, clock=clock,
                  query_log=qlog)
    try:
        futs = [fe.submit(i, RECT) for i in range(2)]

        def waited_for_deadline():
            clock.advance(fe, 0.0)         # wakes a waiting scheduler
            return any(e[0] == "frontend.wait"
                       and e[5]["why"] == "deadline"
                       for e in traced.events())

        _await(waited_for_deadline, what="a deadline wait")
        clock.advance(fe, 10.0)
        assert all(f.result(timeout=10) for f in futs)
    finally:
        fe.close(timeout=10)
    ev = [e for e in traced.events() if e[0].startswith("frontend.")]
    assert {e[2] for e in ev} == {fe._thread.ident}
    by = {}
    for e in ev:
        by.setdefault(e[0], []).append(e)
    assert {e[5]["why"] for e in by["frontend.wait"]} == {"empty",
                                                          "deadline"}
    (flush,), (asm,), (res,), (ql,) = (
        by[f"frontend.{n}"] for n in ("flush", "assemble", "resolve",
                                      "querylog"))
    assert _inside(asm, flush)
    assert flush[3] + flush[4] <= res[3]
    assert res[3] + res[4] <= ql[3]
    assert res[5]["n"] == ql[5]["n"] == 2
    for w in by["frontend.wait"]:
        assert w[3] + w[4] <= flush[3] or w[3] >= ql[3] + ql[4]
    assert qlog.total == 2


def test_querylog_span_only_when_the_log_runs(traced):
    """Without an explicit log and with the tracer off, nothing is
    logged; with the tracer on, the global log runs under its span."""
    eng = BlockableEngine()
    with Frontend(eng, max_batch=2, max_delay=1e-3) as fe:
        fe.submit_many([0, 1], np.tile(RECT, (2, 1)))
    assert [e[0] for e in traced.events()].count("frontend.querylog") == 1
    obs.disable()
    traced.clear()
    with Frontend(eng, max_batch=2, max_delay=1e-3) as fe:
        fe.submit_many([0, 1], np.tile(RECT, (2, 1)))
    assert len(traced) == 0
