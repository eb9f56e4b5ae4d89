"""The program's own host spans, read in the same process and put on the
device trace's clock.

The program keeps its finished spans in memory (``repro.obs``'s
``finished_spans()``: ``name``, ``path``, ``t0`` and ``t1`` on
``time.perf_counter``). The benchmark's ``chipbench.window`` span is
recorded on both clocks: on ``perf_counter`` in ``Run.spans`` and on the
trace clock in ``run.trace.spans``. The straight line through its two
ends maps a program span onto the trace, where it meets the first chip's
idle intervals (``trace.merge`` of its op events).

Every function returns ``None`` where there is nothing to read: no trace,
no window, a program that keeps no spans, or none in the window (the
program run with ``REPRO_OBS=0``).
"""
from __future__ import annotations

import numpy as np

from chipbench import trace


def finished():
    """The program's finished spans, or ``None`` if it keeps none."""
    try:
        from repro import obs
    except ImportError:
        return None
    read = getattr(obs, "finished_spans", None)
    return None if read is None else read()


def _perf_window(run):
    spans = [(s, e) for name, s, e in run.spans if name == trace.WINDOW_SPAN]
    return spans[0] if len(spans) == 1 else None


def in_window(run):
    """The program's finished spans that overlap the window, or ``None``
    (no trace, no window, or no span in it)."""
    win = _perf_window(run)
    spans = finished()
    if run.trace is None or win is None or spans is None:
        return None
    lo, hi = win
    spans = [sp for sp in spans
             if sp.t1 is not None and sp.t1 > lo and sp.t0 < hi]
    return spans or None


def counted(run, name: str):
    """``(spans in the window, number of spans called name)``, or ``None``
    when there is no such span to count by."""
    spans = in_window(run)
    if spans is None:
        return None
    n = sum(sp.name == name for sp in spans)
    return (spans, n) if n else None


def union_s(spans) -> float:
    """Seconds covered by the union of the spans' intervals."""
    if not spans:
        return 0.0
    s, e = trace.merge(np.asarray([sp.t0 for sp in spans], np.float64),
                       np.asarray([sp.t1 for sp in spans], np.float64),
                       -np.inf, np.inf)
    return float(np.sum(e - s))


def on_trace(run, spans):
    """Disjoint sorted intervals (trace ns) covering the spans, clipped
    to the window."""
    p0, p1 = _perf_window(run)
    lo, hi = trace.window(run.trace)
    scale = (hi - lo) / (p1 - p0)
    t0 = np.asarray([sp.t0 for sp in spans], np.float64)
    t1 = np.asarray([sp.t1 for sp in spans], np.float64)
    return trace.merge(lo + (t0 - p0) * scale, lo + (t1 - p0) * scale,
                       lo, hi)


def idle(run):
    """Disjoint sorted idle intervals (trace ns) of the first chip inside
    the window, or ``None`` without op events."""
    if not run.trace.ops:
        return None
    lo, hi = trace.window(run.trace)
    _, st, en = run.trace.ops[sorted(run.trace.ops)[0]]
    s, e = trace.merge(st, en, lo, hi)
    g0, g1 = np.concatenate([[lo], e]), np.concatenate([s, [hi]])
    keep = g1 > g0
    return g0[keep], g1[keep]


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sets of disjoint intervals."""
    (as_, ae), (bs, be) = a, b
    tot = 0.0
    for s, e in zip(bs, be):
        tot += float(np.sum(np.clip(np.minimum(ae, e) - np.maximum(as_, s),
                                    0, None)))
    return tot


def idle_share(run, select, named: bool = True):
    """Percent of the window in which the first chip is idle and (with
    ``named``) a program span that ``select`` keeps is open, or (without)
    none is."""
    spans = in_window(run)
    if spans is None:
        return None
    gaps = idle(run)
    if gaps is None:
        return None
    lo, hi = trace.window(run.trace)
    kept = [sp for sp in spans if select(sp)]
    covered = overlap_ns(gaps, on_trace(run, kept)) if kept else 0.0
    if not named:
        covered = float(np.sum(gaps[1] - gaps[0])) - covered
    return 100.0 * covered / (hi - lo)
