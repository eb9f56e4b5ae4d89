"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

What is read:

* device planes ``/device:TPU:<i>``: the op line (``XLA Ops``) gives the
  intervals in which an operation ran; the module line (``XLA Modules``)
  gives each jitted program's device time by its name;
* every other plane: the benchmark's own host spans, the
  ``jax.profiler.TraceAnnotation`` events whose names start with
  ``chipbench.``, on the same clock.

From them: the window (the ``chipbench.window`` span), busy time as the
union of op intervals inside it (averaged over chips), device time per
program name, the operations that took most time, and the longest idle
gaps, each labelled with the innermost benchmark span open at its middle.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
_PROGRAM_ID = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")


def op_label(hlo: str) -> str:
    """``%fusion.67 fusion`` from an op event's HLO text: the
    instruction's name and its opcode, without shapes and operands."""
    name, _, rest = hlo.partition(" = ")
    m = _OPCODE.search(rest)
    return f"{name} {m.group(1)}" if m else name


@dataclass
class Trace:
    #: per device plane: (names, starts_ns, ends_ns) of its op events
    ops: dict = field(default_factory=dict)
    #: per device plane: (names, starts_ns, ends_ns) of its module events
    modules: dict = field(default_factory=dict)
    #: benchmark host spans: [(name, start_ns, end_ns)]
    spans: list = field(default_factory=list)


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{directory}, found {len(paths)}")
    return paths[0]


def _events(line):
    names, starts, ends = [], [], []
    for ev in line.events:
        names.append(ev.name)
        starts.append(ev.start_ns)
        ends.append(ev.start_ns + ev.duration_ns)
    return (names, np.asarray(starts, np.float64),
            np.asarray(ends, np.float64))


def load(path: str) -> Trace:
    """Read the planes this module needs from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    tr = Trace()
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OP_LINE:
                    tr.ops[plane.name] = _events(line)
                elif line.name == MODULE_LINE:
                    tr.modules[plane.name] = _events(line)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    tr.spans.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return tr


def merge(starts: np.ndarray, ends: np.ndarray, lo: float, hi: float):
    """Disjoint sorted intervals covering the union of [start, end),
    clipped to [lo, hi)."""
    s, e = np.clip(starts, lo, hi), np.clip(ends, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return np.zeros(0), np.zeros(0)
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    new = np.concatenate([[True], s[1:] > e[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(s) - 1]])
    return s[first], e[last]


def window(tr: Trace):
    """(start_ns, end_ns) of the benchmark's window span."""
    spans = [(s, e) for name, s, e in tr.spans if name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0]


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Union of op intervals inside [lo, hi), averaged over the chips."""
    if not tr.ops:
        return 0.0
    tot = [np.sum(e - s) for s, e in
           (merge(st, en, lo, hi) for _, st, en in tr.ops.values())]
    return float(np.mean(tot))


def program_ns(tr: Trace, lo: float, hi: float, match) -> float:
    """Device time, averaged over the chips, of the program (module)
    events inside [lo, hi) whose name (program id stripped) satisfies
    ``match``."""
    if not tr.modules:
        return 0.0
    tot = []
    for names, st, en in tr.modules.values():
        keep = np.asarray([bool(match(_PROGRAM_ID.sub("", n)))
                           for n in names], bool)
        s, e = merge(st[keep], en[keep], lo, hi) if keep.any() else (
            np.zeros(0), np.zeros(0))
        tot.append(np.sum(e - s))
    return float(np.mean(tot))


def top_ops(tr: Trace, lo: float, hi: float, k: int = 10):
    """[[op label, seconds]] of the k operations with the most device
    time inside [lo, hi), summed by label and averaged over the chips."""
    acc: dict = {}
    for names, st, en in tr.ops.values():
        d = np.clip(en, lo, hi) - np.clip(st, lo, hi)
        for n, t in zip(names, d):
            if t > 0:
                n = op_label(n)
                acc[n] = acc.get(n, 0.0) + t
    ndev = max(1, len(tr.ops))
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, float(t / ndev / 1e9)] for n, t in best]


def idle_gaps(tr: Trace, lo: float, hi: float, k: int = 10):
    """[[host span, seconds]] of the k longest gaps between busy intervals
    of the first chip inside [lo, hi), each named by the innermost
    benchmark span open at its middle (``idle`` where none is)."""
    if not tr.ops:
        return []
    _, st, en = tr.ops[sorted(tr.ops)[0]]
    s, e = merge(st, en, lo, hi)
    g0 = np.concatenate([[lo], e])
    g1 = np.concatenate([s, [hi]])
    gaps = sorted(((b - a, a, b) for a, b in zip(g0, g1) if b > a),
                  reverse=True)[:k]
    out = []
    for d, a, b in gaps:
        mid = (a + b) / 2
        open_ = [(ss, name) for name, ss, ee in tr.spans
                 if ss <= mid < ee and name != WINDOW_SPAN]
        out.append([max(open_)[1] if open_ else "idle", float(d / 1e9)])
    return out


def reduce(tr: Trace) -> dict:
    """The window's numbers: ``window_s``, ``busy_s`` and ``breakdown``."""
    lo, hi = window(tr)
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns(tr, lo, hi) / 1e9,
            "breakdown": {"device_ops": top_ops(tr, lo, hi),
                          "idle_gaps": idle_gaps(tr, lo, hi)}}
