"""Telemetry layer contract tests.

* histogram quantiles vs the numpy nearest-rank oracle across
  distributions (exact inside the raw-sample head, bucket-bounded beyond)
* counter/gauge thread-safety under concurrent writers and under
  vmapped shard builds (trace-time increments must not corrupt state)
* disabled mode is a true no-op (no state mutation, no export)
* span nesting, attribute propagation, and event correlation; finished
  spans kept in memory; JAX's trace/lower/compile phases as child spans
  (persistent-cache hits marked); the construction and breaker spans, and
  a shard builder lowered the same with a span open as with obs off
* exporter round trip: snapshot + JSONL events, Prometheus text
* timed_op emits the full ``serve.*`` metric family; track_shapes counts
  distinct signatures once
"""
import contextlib
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import Histogram

bucket_index = Histogram.bucket_index
from repro.obs.report import check_slos, op_rows, render_span_tree


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.REGISTRY.reset()
    obs.reset_shape_tracking()
    yield
    obs.REGISTRY.reset()


# -------------------------------------------------------------------------
# histogram quantiles
# -------------------------------------------------------------------------

@pytest.mark.parametrize("dist", ["uniform", "lognormal", "exponential",
                                  "bimodal"])
def test_quantiles_exact_within_raw_head(dist):
    """With count ≤ raw_cap the quantiles are exact nearest-rank order
    statistics — identical to numpy's inverted_cdf method."""
    rng = np.random.default_rng(hash(dist) % (1 << 31))
    n = 5000
    xs = {
        "uniform": rng.uniform(1e-6, 10.0, n),
        "lognormal": rng.lognormal(-7, 2.5, n),
        "exponential": rng.exponential(0.01, n),
        "bimodal": np.concatenate([rng.normal(1e-4, 1e-5, n // 2),
                                   rng.normal(5.0, 0.5, n - n // 2)]),
    }[dist]
    xs = np.abs(xs) + 1e-9
    h = obs.histogram("t.q", dist=dist)
    for x in xs:
        h.observe(float(x))
    for q in (0.5, 0.9, 0.95, 0.99, 1.0):
        want = float(np.quantile(xs, q, method="inverted_cdf"))
        assert h.quantile(q) == pytest.approx(want, rel=1e-12), (dist, q)
    assert h.count == len(xs)
    assert h.max == pytest.approx(xs.max())
    assert h.min == pytest.approx(xs.min())
    assert h.sum == pytest.approx(xs.sum())


def test_quantiles_bucket_fallback_beyond_cap():
    """Past raw_cap the quantile comes from the log buckets: within one
    bucket's relative width (2^(1/16) ≈ 4.4%) of the true value."""
    rng = np.random.default_rng(7)
    xs = np.abs(rng.lognormal(-5, 2, 30000)) + 1e-9
    h = Histogram("t.big", raw_cap=1024)
    for x in xs:
        h.observe(float(x))
    assert h.count > h.raw_cap
    for q in (0.5, 0.95, 0.99):
        want = float(np.quantile(xs, q, method="inverted_cdf"))
        assert h.quantile(q) == pytest.approx(want, rel=0.05), q


def test_bucket_index_monotone():
    vals = np.logspace(-8, 5, 400)
    idx = [bucket_index(float(v)) for v in vals]
    assert idx == sorted(idx)
    assert bucket_index(0.0) == 0                      # underflow bucket
    assert bucket_index(1e9) == bucket_index(1e8)      # overflow bucket


# -------------------------------------------------------------------------
# thread safety
# -------------------------------------------------------------------------

def test_counter_thread_safety():
    c = obs.counter("t.threads")
    h = obs.histogram("t.threads_h")
    N, T = 2000, 8

    def work():
        for _ in range(N):
            c.inc()
            h.observe(0.001)

    ts = [threading.Thread(target=work) for _ in range(T)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == N * T
    assert h.count == N * T


def test_counters_under_vmap_shard_builds():
    """Trace-time counter increments from inside vmapped/jitted builds
    must leave the registry consistent (and count traces, not calls)."""
    from repro.core.wavelet_matrix import build_wavelet_matrix
    rng = np.random.default_rng(3)
    shards = jnp.asarray(rng.integers(0, 64, (4, 256)).astype(np.uint32))

    def build(s):
        return build_wavelet_matrix(s, 64, sample_rate=128,
                                    use_kernels=False)

    jax.vmap(build)(shards)
    snap = obs.REGISTRY.snapshot()
    builds = {k: v for k, v in snap["counters"].items()
              if k.startswith("core.build")}
    # one vmapped build = ONE trace of the builder
    assert sum(builds.values()) == 1
    key = "core.build{builder=wm,path=fused}"
    assert builds.get(key) == 1


# -------------------------------------------------------------------------
# disabled mode
# -------------------------------------------------------------------------

def test_disabled_mode_is_noop():
    c = obs.counter("t.off")
    h = obs.histogram("t.off_h")
    g = obs.gauge("t.off_g")
    with obs.disabled():
        c.inc(5)
        h.observe(1.0)
        g.set(3.0)
        with obs.span("t.off_span") as sp:
            sp.set("k", "v")        # must not blow up on the null span
            assert sp.sync(42) == 42
        obs.event("t.off_event")
    assert c.value == 0
    assert h.count == 0
    assert g.value is None
    assert "span.t.off_span" not in obs.REGISTRY.snapshot()["histograms"]


def test_disabled_mode_histogram_state_frozen():
    h = obs.histogram("t.frozen")
    h.observe(1.0)
    before = (h.count, h.sum, h.min, h.max)
    with obs.disabled():
        for _ in range(100):
            h.observe(9.0)
    assert (h.count, h.sum, h.min, h.max) == before


# -------------------------------------------------------------------------
# spans
# -------------------------------------------------------------------------

def test_span_nesting_and_attrs():
    with obs.span("outer", a=1) as so:
        assert obs.current_span() is so
        assert so.path == "outer"
        with obs.span("inner") as si:
            assert si.parent_id == so.span_id
            assert si.path == "outer/inner"
            si.set("found", "late")
        assert obs.current_span() is so
    assert obs.current_span() is None
    assert si.attrs["found"] == "late"
    assert so.dur_s >= si.dur_s
    snap = obs.REGISTRY.snapshot()
    assert snap["histograms"]["span.outer"]["count"] == 1
    assert snap["histograms"]["span.inner"]["count"] == 1


def test_span_sync_blocks_on_device_value():
    with obs.span("jitted") as sp:
        out = sp.sync(jnp.arange(8) * 2)
    assert sp.dur_s is not None
    assert int(np.asarray(out)[-1]) == 14


def test_finished_spans_are_kept_in_memory():
    obs.clear_spans()
    with obs.span("outer", a=1) as so:
        with obs.span("inner") as si:
            pass
    got = obs.finished_spans()
    assert [sp.name for sp in got] == ["inner", "outer"]
    assert got[0] is si and got[1] is so
    assert so.t0 <= si.t0 <= si.t1 <= so.t1
    assert si.parent_id == so.span_id and so.attrs == {"a": 1}
    assert so.dur_s == pytest.approx(so.t1 - so.t0)
    assert so.ts > 1e9                   # wall clock, Unix seconds
    obs.clear_spans()
    assert obs.finished_spans() == []


def _fresh_jit(k):
    """A function JAX has not traced yet: jitting it traces, lowers and
    compiles anew (the same program for every call)."""
    def fresh(x):
        return jnp.sin(x) * k + jnp.cumsum(x)
    return jax.jit(fresh)


def _children(parent):
    return {sp.name: sp for sp in obs.finished_spans()
            if sp.parent_id == parent.span_id}


def test_jax_compile_phases_are_child_spans():
    obs.clear_spans()
    with obs.span("compiling") as parent:
        _fresh_jit(3.0)(jnp.arange(16.0)).block_until_ready()
    kids = _children(parent)
    assert {"jax.trace", "jax.lower", "jax.compile"} <= set(kids)
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        sp = kids[name]
        assert parent.t0 <= sp.t0 <= sp.t1 <= parent.t1
        assert sp.path == f"compiling/{name}"
        assert "fresh" in sp.attrs["fun"]
    assert kids["jax.trace"].t1 <= kids["jax.lower"].t0 + 1e-3
    assert kids["jax.compile"].attrs["cache_hit"] is False
    snap = obs.REGISTRY.snapshot()["counters"]
    assert snap.get("jax.compiles{cache=miss}", 0) >= 1


@pytest.fixture
def persistent_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    yield tmp_path
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_persistent_cache_hit_marks_the_compile_span(persistent_cache):
    x = jnp.arange(16.0)
    hits = []
    for _ in range(2):                  # the second copy loads the first
        obs.clear_spans()
        with obs.span("compiling") as parent:
            _fresh_jit(5.0)(x).block_until_ready()
        hits.append(_children(parent)["jax.compile"].attrs["cache_hit"])
    assert hits == [False, True]
    snap = obs.REGISTRY.snapshot()["counters"]
    assert snap.get("jax.compiles{cache=hit}") == 1


def test_disabled_mode_records_no_spans_and_no_phases():
    obs.clear_spans()
    with obs.disabled():
        with obs.span("compiling"):
            _fresh_jit(7.0)(jnp.arange(16.0)).block_until_ready()
    assert obs.finished_spans() == []
    assert not any(k.startswith("jax.compiles")
                   for k in obs.REGISTRY.snapshot()["counters"])


def test_compile_work_outside_any_span_is_not_recorded():
    obs.clear_spans()
    with obs.span("first"):             # registers the JAX listener
        pass
    obs.clear_spans()
    _fresh_jit(9.0)(jnp.arange(16.0)).block_until_ready()
    assert obs.finished_spans() == []


@pytest.mark.parametrize("parallel,mode", [(False, "loop"), (True, "vmap")])
def test_store_build_spans(parallel, mode):
    from repro.data.compressed_store import build_compressed_corpus
    toks = np.random.default_rng(5).integers(0, 50, 3000).astype(np.uint32)
    for _ in range(2):                  # the second build is the warm one
        obs.clear_spans()
        corpus = build_compressed_corpus(toks, 50, shard_bits=10,
                                         sample_rate=128, parallel=parallel)
        jax.block_until_ready(corpus)
    spans = obs.finished_spans()
    (build,) = [sp for sp in spans if sp.name == "store.build"]
    assert build.attrs == {"shards": 3, "sigma": 50, "mode": mode}
    kids = [sp for sp in spans if sp.parent_id == build.span_id]
    assert [sp.name for sp in kids] == ["store.build.shards",
                                        "store.build.stack",
                                        "store.build.histogram"]
    for sp in kids:
        assert build.t0 <= sp.t0 <= sp.t1 <= build.t1
    phases = {sp.name for sp in spans if sp.name.startswith("jax.")}
    assert all(sp.path.startswith("store.build/") for sp in spans
               if sp.name.startswith("jax."))
    if mode == "loop":                  # a fresh builder: traced anew
        assert {"jax.trace", "jax.lower", "jax.compile"} <= phases
        assert len(spans) <= 8          # a bounded cost per build


def test_spans_leave_the_shard_builder_program_unchanged(monkeypatch):
    """The lowered shard builder, debug locations included, is the same
    with a ``store.build`` span open as with obs disabled."""
    from repro.data import compressed_store
    real = compressed_store.build_shards_stacked
    texts = []

    def lowering(build_one, shards, **kw):
        assert obs.current_span() is None or \
            obs.current_span().name == "store.build"
        texts.append(jax.jit(build_one).lower(
            jnp.asarray(shards[0])).as_text(debug_info=True))
        return real(build_one, shards, **kw)

    monkeypatch.setattr(compressed_store, "build_shards_stacked", lowering)
    toks = np.random.default_rng(6).integers(0, 40, 2048).astype(np.uint32)
    for ctx in (obs.disabled, contextlib.nullcontext):
        with ctx():
            compressed_store.build_compressed_corpus(toks, 40, shard_bits=10,
                                                     sample_rate=128)
    assert len(texts) == 2
    assert "jit_wm_shard_build" in texts[0]
    assert texts[0] == texts[1]


def test_breakers_refresh_span_counts_probes():
    from repro.robust.clock import FakeClock
    from repro.serving.breakers import ShardBreakers
    clock = FakeClock()
    br = ShardBreakers(4, lambda s: True, clock=clock)
    obs.clear_spans()
    br.refresh()                        # every shard is due
    br.refresh()                        # none is, yet
    clock.advance(1.0)
    br.refresh()
    got = [sp for sp in obs.finished_spans()
           if sp.name == "frontend.breakers.refresh"]
    assert [sp.attrs["probes"] for sp in got] == [4, 0, 4]
    assert obs.counter("serve.frontend.probes").value == 8


# -------------------------------------------------------------------------
# export + report
# -------------------------------------------------------------------------

def test_export_roundtrip_and_span_tree(tmp_path):
    obs.configure(tmp_path)
    try:
        with obs.span("load"):
            with obs.span("verify"):
                obs.event("fault.test", kind="fault", leaf="rank/words")
        obs.timed_op("analytics", "quantile",
                     lambda x: jnp.sum(x), jnp.arange(100), batch=100)
        obs.write_snapshot()
    finally:
        obs.configure(None)

    snap = obs.read_snapshot(tmp_path)
    assert "serve.analytics.quantile.latency_s" in snap["histograms"]
    assert snap["meta"]["jax_version"] == jax.__version__

    events = obs.read_events(tmp_path)
    kinds = {e["kind"] for e in events}
    assert {"span", "fault"} <= kinds
    tree = render_span_tree(events)
    lines = tree.splitlines()
    assert lines[0].startswith("load")
    assert any("verify" in ln for ln in lines)
    # the fault event is nested under the verify span, deeper than it
    fault_ln = next(ln for ln in lines if "fault.test" in ln)
    verify_ln = next(ln for ln in lines if ln.lstrip().startswith("verify"))
    assert (len(fault_ln) - len(fault_ln.lstrip())
            > len(verify_ln) - len(verify_ln.lstrip()))

    rows = op_rows(snap)
    assert [r.op for r in rows] == ["analytics.quantile"]
    assert rows[0].batch == 100
    ok = check_slos(rows, ["analytics.*:p99_ms<=60000"])
    assert ok and all(r.ok for r in ok)
    bad = check_slos(rows, ["analytics.*:qps>=1e18"])
    assert any(not r.ok for r in bad)
    missing = check_slos(rows, ["nosuch.*:p99_ms<=1"])
    assert any(not r.ok for r in missing)   # no-match = violation

    prom = obs.prometheus_text(snap)
    assert "serve_analytics_quantile_latency_s" in prom.replace(".", "_")


def test_jsonl_skips_torn_lines(tmp_path):
    obs.configure(tmp_path)
    try:
        obs.event("fine")
    finally:
        obs.configure(None)
    with open(tmp_path / "events.jsonl", "a") as f:
        f.write('{"ts": 1, "kind": "event", "name": "torn...')
    events = obs.read_events(tmp_path)
    assert [e["name"] for e in events] == ["fine"]


def test_timed_op_metric_family():
    obs.timed_op("index", "count", lambda x: x + 1, jnp.arange(16),
                 batch=16, iters=2)
    snap = obs.REGISTRY.snapshot()
    assert snap["histograms"]["serve.index.count.latency_s"]["count"] == 1
    assert snap["gauges"]["serve.index.count.batch"] == 16
    assert snap["gauges"]["serve.index.count.compile_s"] > 0
    assert snap["counters"]["serve.index.count.calls"] == 3
    assert snap["counters"]["jit.shapes{op=index.count}"] == 1


def test_track_shapes_counts_distinct_signatures():
    assert obs.track_shapes("op", jnp.zeros((4,))) is True
    assert obs.track_shapes("op", jnp.zeros((4,))) is False
    assert obs.track_shapes("op", jnp.zeros((8,))) is True
    assert obs.track_shapes("op", jnp.zeros((8,), jnp.int32)) is True
    snap = obs.REGISTRY.snapshot()
    assert snap["counters"]["jit.shapes{op=op}"] == 3
    assert snap["counters"]["jit.calls{op=op}"] == 4


def test_key_roundtrip():
    c = obs.counter("a.b", z="1", a="2")
    assert c.key == "a.b{a=2,z=1}"          # labels sorted
    name, labels = obs.parse_key(c.key)
    assert name == "a.b" and labels == {"a": "2", "z": "1"}


# per https://prometheus.io/docs/instrumenting/exposition_formats/:
# metric names [a-zA-Z_:][a-zA-Z0-9_:]*, label names [a-zA-Z_][a-zA-Z0-9_]*,
# label values with \\, \" and \n escaped, sample value a float
_PROM_LINE = (r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
              r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*"'
              r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*")*\})?'
              r' -?[0-9.eE+-]+(\.[0-9]+)?$')


def test_prometheus_text_is_valid_exposition_format():
    """Every emitted line must parse under the exposition-format grammar,
    including metric names with dots/dashes and label values containing
    quotes, backslashes and newlines."""
    import re
    obs.counter("kernels.trace", op="wm-level_step", interpret="false").inc()
    obs.counter("prof.bound", op="analytics.quantile", term="memory").inc()
    obs.gauge("prof.roofline_util", op="analytics.quantile").set(0.42)
    obs.gauge("weird-name.metric", path='a"b\\c\nd').set(-1.5e-3)
    obs.histogram("serve.analytics.quantile.latency_s").observe(0.01)
    snap = obs.REGISTRY.snapshot()
    text = obs.prometheus_text(snap)
    line_re = re.compile(_PROM_LINE)
    lines = [ln for ln in text.splitlines() if ln]
    assert lines, "prometheus_text produced no samples"
    for ln in lines:
        assert line_re.match(ln), f"invalid exposition line: {ln!r}"
        float(ln.rsplit(" ", 1)[1])        # sample value parses
    # dots in names become underscores, label values keep their content
    assert any(ln.startswith("kernels_trace_total{") for ln in lines)
    assert any("prof_roofline_util" in ln and "0.42" in ln for ln in lines)
    assert any(r'path="a\"b\\c\nd"' in ln for ln in lines)
    # histograms expand to _count/_sum + quantile samples
    assert any("serve_analytics_quantile_latency_s_count" in ln
               for ln in lines)
