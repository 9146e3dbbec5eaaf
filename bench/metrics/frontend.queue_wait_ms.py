"""Mean milliseconds from a request's enqueue to the start of its batch's
flush, on the Frontend's own clock: the sum of the ``wait_us_sum`` args
of the program spans ``frontend.flush`` over the sum of their ``n``."""


def read(run):
    flushes = [e[5] for e in run.program_spans("frontend.flush")
               if e[5] and "wait_us_sum" in e[5]]
    n = sum(a["n"] for a in flushes)
    return sum(a["wait_us_sum"] for a in flushes) / 1e3 / n if n else None
