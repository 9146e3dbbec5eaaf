"""Device idle time that no program span covers, from a profiler trace.

The program opens its spans as ``jax.profiler`` annotations too, so they
stand on the host thread lines of the same trace as the device's ops,
on one clock.  A moment of the window counts as idle and unspanned when
no op runs on the device and no program span (``frontend.*``,
``cluster.*``) is open on any host thread: the union of the device's op
intervals and every program span, taken away from the window.  Unlike
``trace_reduce.reduce``'s breakdown, which names each whole gap after
the span open at its midpoint, this intersects intervals, so a gap that
a span covers only in part counts only its uncovered part.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce

PREFIXES = ("frontend.", "cluster.")


def host_spans(trace: Dict, prefixes: Sequence[str] = PREFIXES
               ) -> List[Tuple[float, float]]:
    """(start_ns, end_ns) of every program span on the trace's host
    planes, over all threads."""
    prefixes = tuple(prefixes)
    return [(s, s + d) for evs in trace["host"].values()
            for _line, name, s, d in evs if name.startswith(prefixes)]


def idle_unspanned(trace: Dict,
                   window: Optional[Tuple[float, float]] = None,
                   prefixes: Sequence[str] = PREFIXES
                   ) -> Optional[Tuple[float, float]]:
    """(idle and unspanned seconds, window seconds), averaged over the
    device planes that ran ops in the window; ``None`` where the trace
    holds no program span or no device op in it."""
    t0, t1 = window if window is not None else trace_reduce.window_of(trace)

    def clip(iv):
        return [(max(a, t0), min(b, t1)) for a, b in iv
                if min(b, t1) > max(a, t0)]

    spans = clip(host_spans(trace, prefixes))
    if not spans:
        return None
    idle, n_dev = 0.0, 0
    for evs in trace["device"].values():
        ops = clip((s, s + d) for _line, _name, s, d in evs)
        if not ops:
            continue
        n_dev += 1
        covered = trace_reduce._union(ops + spans)
        idle += (t1 - t0) - sum(b - a for a, b in covered)
    if not n_dev:
        return None
    return idle / n_dev * 1e-9, (t1 - t0) * 1e-9
