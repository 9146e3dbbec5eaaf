"""Device-to-host transfers the engine's fused path made per batch in
the window (``ShardedEngine.stats``: ``host_fetches`` / ``batches``):
one a batch, one more a capacity re-run.  ``None`` for an engine that
does not count them."""


def read(run):
    n = run.engine.get("batches", 0)
    if "host_fetches" not in run.engine or not n:
        return None
    return run.engine["host_fetches"] / n
