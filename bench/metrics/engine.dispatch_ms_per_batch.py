"""Host milliseconds per batch in the engine's call of its collective
program, until the call returns: the program span ``cluster.dispatch``
(inside ``cluster.fused``, before ``cluster.block`` waits on the device)
per ``cluster.query_batch``."""


def read(run):
    batches = len(run.program_spans("cluster.query_batch"))
    spans = run.program_spans("cluster.dispatch")
    if not batches or not spans:
        return None
    return sum(e[4] for e in spans) / 1e6 / batches
