"""The reader of the engine's transfer counter: on the counters of a real
engine's window, and on an engine that does not count transfers."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from bench import spec  # noqa: E402
from bench import run as bench_run  # noqa: E402


def engine_run(engine_stats):
    """A ``Run`` holding only the engine's counters over a window."""
    run = object.__new__(bench_run.Run)
    run.engine = dict(engine_stats)
    return run


@pytest.mark.parametrize("suffix", [".open", ".closed"])
def test_fetches_per_batch_over_a_window(suffix):
    from repro.cluster import ShardedEngine
    from repro.core import build_2dreach
    from repro.data import get_dataset, workload

    g = get_dataset("yelp", scale=0.05)
    eng = ShardedEngine(build_2dreach(g, variant="comp"), n_shards=1)
    us, rects = workload(g, 64, extent_ratio=0.05, seed=1)
    eng.query_batch(us, rects)
    before = dict(eng.stats)
    for seed in range(2, 6):
        eng.query_batch(*workload(g, 64, extent_ratio=0.05, seed=seed))
    window = {k: eng.stats[k] - before[k] for k in before}
    assert window["batches"] == 4
    read = spec.reader("engine.fetches_per_batch" + suffix)
    # one copy a batch, one more a capacity re-run
    assert read(engine_run(window)) == pytest.approx(
        1 + window["fused_reruns"] / window["batches"])


def test_fetches_per_batch_finds_nothing_without_the_counter():
    """An engine without ``host_fetches``, or a window with no batch,
    gives no reading, and raises nothing."""
    read = spec.reader("engine.fetches_per_batch.open")
    older = {"batches": 12, "queries": 40, "fused_reruns": 0}
    assert read(engine_run(older)) is None
    assert read(engine_run({})) is None
    assert read(engine_run({"batches": 0, "host_fetches": 0})) is None
    assert read(engine_run({"batches": 4, "host_fetches": 5})) == \
        pytest.approx(1.25)
