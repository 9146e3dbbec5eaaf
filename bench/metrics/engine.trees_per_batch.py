"""Distinct index trees the real queries of a batch route to, per batch
in the window (``ShardedEngine.stats``: ``trees_routed`` / ``batches``):
how far a cell's batches spread over the arena.  ``None`` for an engine
that does not count them."""


def read(run):
    n = run.engine.get("batches", 0)
    if "trees_routed" not in run.engine or not n:
        return None
    return run.engine["trees_routed"] / n
