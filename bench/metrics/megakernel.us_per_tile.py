"""Device microseconds the fused serving megakernel spends per leaf tile
it walks: its time per batch, read as ``megakernel.device_ms_per_batch``
reads it, over the candidate tiles per batch (``ShardedEngine.stats``:
``tiles_scanned`` / ``batches``).  ``None`` without a trace, without the
kernel in it, or without the engine's tile counter."""

from bench import trace_reduce


def read(run):
    r = run.reduced()
    spans = len(run.program_spans("cluster.query_batch"))
    batches = run.engine.get("batches", 0)
    tiles = run.engine.get("tiles_scanned", 0)
    if r is None or not spans or not batches or not tiles:
        return None
    s, n = trace_reduce.op_time(r, trace_reduce.MEGAKERNEL)
    return (s * 1e6 / spans) / (tiles / batches) if n else None
