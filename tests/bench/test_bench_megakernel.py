"""The reader of the megakernel's device time a walked leaf tile, on the
CPU: a synthetic run whose trace holds the kernel's events inside the
measured window, beside the engine's batch and tile counters."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from bench import spec, trace_reduce  # noqa: E402
from bench import run as bench_run  # noqa: E402


def kernel_run(engine_stats, batches=4, kernel_ns=(7_000_000,) * 4):
    """A ``Run`` holding a trace of megakernel events inside the window,
    ``batches`` ``cluster.query_batch`` spans and the engine's counters."""
    run = object.__new__(bench_run.Run)
    run.engine = dict(engine_stats)
    run.spans = [("cluster.query_batch", "", 1, 10_000_000 * i, 9_000_000,
                  None) for i in range(batches)]
    dev = [("XLA Ops", "fused_serve_pallas.1", 10_000_000.0 * i + 1e6,
            float(d)) for i, d in enumerate(kernel_ns)]
    dev.append(("XLA Ops", "fusion.3", 5e5, 2e5))
    run.trace = {"device": {"/device:TPU:0": dev}, "host": {"/host:CPU": [
        ("thread", trace_reduce.WINDOW, 0.0, 10_000_000.0 * batches)]}}
    run.trace_offset_ns = 0.0
    run._reduced = None
    return run


@pytest.mark.parametrize("suffix", [".open", ".closed"])
def test_us_per_tile_over_a_window(suffix):
    """The megakernel's device time a batch over the tiles a batch:
    4 batches of 7 ms each over 4 x 5,000 tiles, 1.4 us a tile; no
    reading without a trace, the kernel's events, the batch spans or
    the engine's tile counter, and nothing raised."""
    read = spec.reader("megakernel.us_per_tile" + suffix)
    stats = {"batches": 4, "tiles_scanned": 20_000}
    assert read(kernel_run(stats)) == pytest.approx(1.4)
    assert read(kernel_run(stats, kernel_ns=(6e6, 8e6, 7e6, 7e6))) == \
        pytest.approx(1.4)
    no_trace = kernel_run(stats)
    no_trace.trace = None
    assert read(no_trace) is None
    assert read(kernel_run(stats, kernel_ns=())) is None
    assert read(kernel_run(stats, batches=0)) is None
    assert read(kernel_run({"batches": 4, "queries": 40})) is None
    assert read(kernel_run({})) is None
    assert read(kernel_run({"batches": 4, "tiles_scanned": 0})) is None
