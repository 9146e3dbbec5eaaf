"""Share (%) of the window in which no operation ran on the device and no
program span (``frontend.*``, ``cluster.*``) was open on any host thread,
by interval intersection on the profiler trace's own clock
(``bench/idle_spans.py``).  Nothing to read where the program puts no
span into the trace."""

from bench import idle_spans


def read(run):
    if run.trace is None:
        return None
    r = idle_spans.idle_unspanned(run.trace)
    if r is None or r[1] <= 0:
        return None
    return 100.0 * r[0] / r[1]
