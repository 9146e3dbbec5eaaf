"""Host milliseconds per batch in the Frontend's resolve loop: the program
span ``frontend.resolve`` (every future's result set, with the client
callbacks it runs) per ``cluster.query_batch``."""


def read(run):
    batches = len(run.program_spans("cluster.query_batch"))
    spans = run.program_spans("frontend.resolve")
    if not batches or not spans:
        return None
    return sum(e[4] for e in spans) / 1e6 / batches
