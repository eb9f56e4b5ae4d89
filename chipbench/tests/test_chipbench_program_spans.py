"""The readers of the program's own spans, on a hand-made run: a trace
whose window lies 5 s into the trace clock while the same window lies
100 s into ``perf_counter``, one chip busy in three stretches, and the
program's spans of two builds, with spans before the window that must
not count."""
import numpy as np
import pytest

from chipbench import run, trace
from repro import obs
from repro.obs.spans import Span

S = 1e9                      # trace ns per second
P0 = 100.0                   # the window's start on perf_counter
T0 = 5 * S                   # ... and on the trace clock


def _span(name, path, t0, t1):
    sp = Span(name, path, {}, None)
    sp.t0, sp.t1 = t0, t1
    sp.dur_s = t1 - t0
    return sp


def _build(t0, t1, shards, phases, hist):
    out = [_span("store.build", "store.build", t0, t1),
           _span("store.build.shards", "store.build/store.build.shards",
                 *shards),
           _span("store.build.histogram",
                 "store.build/store.build.histogram", *hist)]
    for name, a, b in phases:
        out.append(_span(name, f"store.build/store.build.shards/{name}",
                         a, b))
    return out


#: before the window (set-up's warm build): read by nothing
SETUP = _build(90.0, 95.0, (90.0, 93.0), [("jax.trace", 90.0, 91.0)],
               (94.0, 95.0))
BUILD1 = _build(100.2, 104.8, (100.2, 103.2),
                [("jax.trace", 101.0, 101.5), ("jax.lower", 101.5, 102.0),
                 ("jax.compile", 102.0, 102.5)], (103.3, 104.3))
BUILD2 = _build(105.5, 109.5, (105.5, 108.0),
                [("jax.trace", 105.5, 106.0), ("jax.trace", 105.8, 105.9),
                 ("jax.lower", 106.0, 106.5), ("jax.compile", 106.5, 107.5)],
                (108.5, 109.0))


def _run(spans=None, with_trace=True):
    """Busy on the trace clock at [5, 6), [8, 9) and [12, 15) s: idle
    [6, 8) and [9, 12), i.e. perf_counter [101, 103) and [104, 107)."""
    tr = trace.Trace(
        ops={"/device:TPU:0": (["a", "b", "c"],
                               np.array([5.0, 8.0, 12.0]) * S,
                               np.array([6.0, 9.0, 15.0]) * S)},
        spans=[("chipbench.window", T0, T0 + 10 * S),
               ("chipbench.build", T0 + 0.2 * S, T0 + 4.8 * S)])
    return run.Run("pile_vocab17.ingest", {}, {}, 1, {}, {"builds": 2},
                   [("chipbench.build", P0 + 0.2, P0 + 4.8),
                    ("chipbench.window", P0, P0 + 10.0)],
                   tr if with_trace else None)


@pytest.fixture
def program(monkeypatch):
    def keep(spans):
        monkeypatch.setattr(obs, "finished_spans", lambda: list(spans))
    return keep


# every value from the intervals above by hand:
# jit: union of the phases, 1.5 s + 2.0 s (the inner trace counts once)
# histogram: 1.0 s + 0.5 s; idle with a phase open: [101, 102.5) and
# [105.5, 107), 3.0 s of 10; idle outside every span: [104.8, 105.5)
@pytest.mark.parametrize("metric,want", [
    ("jit_ms.build", 1750.0),
    ("histogram_ms.build", 750.0),
    ("idle_jit.build", 30.0),
    ("idle_unnamed.build", 7.0),
])
def test_reader_reads_the_program_spans_of_the_window(program, metric,
                                                      want):
    program(SETUP + BUILD1 + BUILD2)
    assert run.load_metric(metric)(_run()) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("metric", ["jit_ms.build", "histogram_ms.build",
                                    "idle_jit.build", "idle_unnamed.build",
                                    "refresh_ms.query"])
def test_reader_gives_none_without_spans_or_trace(program, monkeypatch,
                                                  metric):
    read = run.load_metric(metric)
    program(SETUP)                          # none inside the window
    assert read(_run()) is None
    program(SETUP + BUILD1 + BUILD2)
    assert read(_run(with_trace=False)) is None
    monkeypatch.delattr(obs, "finished_spans")  # a program without them
    assert read(_run()) is None


def test_refresh_ms_per_pump(program):
    pumps = [_span("frontend.pump", "frontend.pump", a, a + 0.2)
             for a in (101.0, 102.0, 103.0, 104.0)]
    refresh = [_span("frontend.breakers.refresh",
                     "frontend.pump/frontend.breakers.refresh", a, a + d)
               for a, d in ((101.0, 0.1), (103.0, 0.02))]
    program(pumps + refresh)
    got = run.load_metric("refresh_ms.query")(_run())
    assert got == pytest.approx(1e3 * 0.12 / 4, rel=1e-9)
