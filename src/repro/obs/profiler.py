"""Device-stage profiling: opt-in ``jax.profiler`` capture + a kernel
cost model so a measured latency always ships with the work it bought.

The span tracer attributes *host wall time* per stage; this module adds
the device side: :func:`device_trace` wraps a serving pass in a JAX
profiler capture (TensorBoard-loadable; per-kernel HLO timings on real
accelerators), and :func:`engine_cost_model` turns an engine's tile
counters into first-order cost terms — bytes the leaf scan touched,
candidate tiles that survived the hierarchical prune, the fraction of a
full arena scan actually paid — so a kernel-latency regression in
BENCH_*.json is explainable (did the prune get worse, or the kernel?).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax


@contextlib.contextmanager
def device_trace(logdir: str, enabled: bool = True):
    """Opt-in ``jax.profiler`` capture around a serving pass.

    No-op when ``enabled`` is False.  A requested capture that cannot
    start (a second concurrent capture, a backend without profiler
    support) raises: a run asked to trace must not pass untraced.
    """
    if not enabled:
        yield
        return
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named region visible inside a ``device_trace`` capture
    (``jax.profiler.TraceAnnotation``); falls back to a null context on
    runtimes without it."""
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return contextlib.nullcontext()


def engine_cost_model(engine) -> dict:
    """First-order per-batch cost terms from an engine's tile counters.

    Works for both :class:`~repro.core.engine.QueryEngine` and
    :class:`~repro.cluster.ShardedEngine` (their ``stats`` share the
    tile-counter schema).  All ``*_per_batch`` terms are lifetime means.

    Terms
    -----
    candidate_tiles_per_batch:  leaf tiles that survived the prune —
        the work an ideal scan does.
    grid_tiles_per_batch:       kernel grid steps incl. K-bucket
        padding (padded steps repeat a tile; their DMA is elided).
    scan_bytes_per_batch:       entry-plane bytes the scan grid touches
        (TP entries x 2*dim float32 planes per tile).
    prune_bytes_per_batch:      tile-MBR pyramid bytes the prune reads
        (fine + coarse float32 planes of every shard): the kernel holds
        the pyramid for the whole call, so it is read once per call —
        once per batch, plus once per capacity re-run.
    scan_fraction:              candidate tiles / full-arena scan — the
        prune's effectiveness; 1.0 means pruning bought nothing.
    """
    from ..kernels.range_query.descent import COARSE_GROUP
    from ..kernels.range_query.kernel import TB, TP

    stats = engine.stats
    batches = max(int(stats.get("batches", 0)), 1)
    dim = int(getattr(engine, "dim", 2))
    n_tiles = int(getattr(engine, "n_tiles", 0))
    n_shards = int(getattr(engine, "n_shards", 1))
    planes = 2 * dim
    tile_bytes = TP * planes * 4
    cand = stats.get("tiles_scanned", 0) / batches
    grid = stats.get("tiles_grid", 0) / batches
    full = stats.get("tiles_full_scan", 0) / batches
    pyramid_tiles = n_tiles * n_shards * (1 + 1 / max(COARSE_GROUP, 1))
    calls = 1 + stats.get("fused_reruns", 0) / batches
    return {
        "batches": int(stats.get("batches", 0)),
        "queries_per_batch": stats.get("queries", 0) / batches,
        "candidate_tiles_per_batch": cand,
        "grid_tiles_per_batch": grid,
        "full_scan_tiles_per_batch": full,
        "scan_fraction": cand / full if full else None,
        "scan_bytes_per_batch": grid * tile_bytes,
        "prune_bytes_per_batch": calls * pyramid_tiles * planes * 4,
        "tile_shape": {"TB": TB, "TP": TP, "planes": planes},
    }
