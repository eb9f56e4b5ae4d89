"""Device-profiling layer contract tests.

* ``profile_op``/``profiled_op`` read the XLA cost model off an AOT
  compile and record the full ``prof.*`` gauge family (flops, bytes,
  arithmetic intensity, achieved rates, roofline utilization, peak
  working set) — asserted end-to-end for the acceptance paths: the
  analytics quantile op, the construction path, and the Pallas kernel
  descent, with the gauges surviving a snapshot.json round trip.
* the hardware model is keyed by ``device_kind`` (the v5e entry carries
  its published peaks), raises for a kind it does not list, and honors
  env overrides; utilization is bound_time / measured_time so it must
  land in (0, 1] on a sane run.
* a failing op raises out of ``profile_op`` (no silent error record).
* ``analyze_hlo`` stays importable from its old ``launch.hlo_analysis``
  home (back-compat shim) and agrees with the moved implementation.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.obs.prof import (PEAKS, compiled_cost, compiled_memory,
                            hw_model, live_memory_stats, profile_op,
                            profiled_op, record_memory_gauges)


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.REGISTRY.reset()
    obs.reset_shape_tracking()
    yield
    obs.REGISTRY.reset()


def test_hw_model_env_override(monkeypatch):
    peak, bw = hw_model("cpu")
    assert (peak, bw) == PEAKS["cpu"]
    assert hw_model() == PEAKS[jax.devices()[0].device_kind]
    monkeypatch.setenv("REPRO_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("REPRO_HBM_BW", "2e11")
    assert hw_model("cpu") == (1e12, 2e11)
    assert hw_model("TPU v5 lite") == (1e12, 2e11)   # override wins everywhere


def test_hw_model_v5e_published_peaks():
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    assert hw_model("TPU v5 lite") == (197e12, 819e9)


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "tpu", "gpu",
                                  "NVIDIA A100-SXM4-40GB"])
def test_hw_model_unknown_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        hw_model(kind)


def test_compiled_cost_and_memory_of_matmul():
    compiled = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((64, 64)), jnp.ones((64, 64))).compile()
    cost = compiled_cost(compiled)
    # 2·64³ FLOPs for the dot, 3·64²·4 bytes in+out
    assert cost["flops"] == pytest.approx(2 * 64 ** 3)
    assert cost["bytes_accessed"] >= 3 * 64 * 64 * 4
    mem = compiled_memory(compiled)
    assert mem["peak_bytes"] > 0
    assert mem["peak_bytes"] == pytest.approx(
        mem["argument_bytes"] + mem["output_bytes"] + mem["temp_bytes"]
        - mem["alias_bytes"])


def test_profile_op_records_roofline_family():
    out, stats = profile_op("t.mm", lambda a, b: a @ b,
                            jnp.ones((32, 32)), jnp.ones((32, 32)),
                            iters=2, work_elements=32 * 32)
    assert out is not None and "error" not in stats
    assert stats["flops"] == pytest.approx(2 * 32 ** 3)
    assert 0 < stats["roofline_util"] <= 1.0
    assert stats["bound"] in ("compute", "memory")
    assert stats["melem_per_s"] > 0
    snap = obs.REGISTRY.snapshot()
    g = snap["gauges"]
    for field in ("flops", "bytes_accessed", "ai", "achieved_flops_s",
                  "roofline_util", "peak_bytes", "steady_s",
                  "melem_per_s"):
        assert f"prof.{field}{{op=t.mm}}" in g, field
    assert snap["counters"][
        f"prof.bound{{op=t.mm,term={stats['bound']}}}"] == 1


def test_profile_op_nonstrict_degrades():
    def boom(x):
        raise RuntimeError("nope")
    with pytest.raises(RuntimeError, match="nope"):
        profile_op("t.bad", boom, jnp.ones(4))
    with pytest.raises(RuntimeError, match="nope"):
        profiled_op("t", "bad", boom, jnp.ones(4))
    snap = obs.REGISTRY.snapshot()
    assert not any(k.startswith("prof.") for k in snap["gauges"])


def test_profiled_op_emits_both_families():
    out, steady_s, compile_s = profiled_op(
        "analytics", "mm", lambda a, b: a @ b,
        jnp.ones((16, 16)), jnp.ones((16, 16)), batch=16, iters=2)
    assert out is not None and steady_s > 0 and compile_s > 0
    snap = obs.REGISTRY.snapshot()
    assert snap["histograms"]["serve.analytics.mm.latency_s"]["count"] == 1
    assert snap["gauges"]["serve.analytics.mm.batch"] == 16
    assert snap["counters"]["serve.analytics.mm.calls"] == 3
    assert "prof.roofline_util{op=analytics.mm}" in snap["gauges"]


def test_memory_gauges():
    keep = jnp.ones((256, 256))            # held alive across the snapshot
    stats = record_memory_gauges()
    assert stats["live_arrays"] >= 1
    assert stats["live_bytes"] >= keep.size * keep.dtype.itemsize
    snap = obs.REGISTRY.snapshot()
    assert snap["gauges"]["prof.mem.live_arrays"] >= 1
    assert live_memory_stats()["live_bytes"] > 0


def test_acceptance_paths_in_snapshot(tmp_path):
    """The quantile, construction, and kernel paths must all land
    roofline-utilization and peak-memory gauges in snapshot.json."""
    from repro.analytics import build_sharded_analytics
    from repro.core.wavelet_matrix import build_wavelet_matrix
    from repro.data import make_corpus

    toks = np.asarray(make_corpus(1 << 12, 256, seed=0), np.int64)
    eng = build_sharded_analytics(toks, 256, shard_bits=10)
    lo = jnp.arange(8, dtype=jnp.int32)
    hi = lo + 64
    k = jnp.full((8,), 3, jnp.int32)

    _, s_q = profile_op("analytics.quantile",
                        lambda e, a, b, c: e.range_quantile(a, b, c),
                        eng, lo, hi, k, work_elements=8.0)
    _, s_k = profile_op(
        "analytics.quantile_kernel",
        lambda e, a, b, c: e.range_quantile(a, b, c, use_kernel=True),
        eng, lo, hi, k, work_elements=8.0)
    sub = jnp.asarray(toks[:1024], jnp.int32)
    _, s_c = profile_op("analytics.construct_shard",
                        lambda s: build_wavelet_matrix(s, 256), sub,
                        work_elements=1024.0)
    for s in (s_q, s_k, s_c):
        assert "error" not in s, s
        assert 0 < s["roofline_util"] <= 1.0
        assert s["peak_bytes"] > 0

    obs.write_snapshot(tmp_path)
    snap = obs.read_snapshot(tmp_path)
    for op in ("analytics.quantile", "analytics.quantile_kernel",
               "analytics.construct_shard"):
        assert snap["gauges"][f"prof.roofline_util{{op={op}}}"] > 0
        assert snap["gauges"][f"prof.peak_bytes{{op={op}}}"] > 0
    assert snap["gauges"]["prof.mem.live_bytes"] > 0


def test_kernel_work_gauges():
    """The jitted kernel wrappers record trace-time work-size gauges."""
    from repro.kernels.ops import bitpack
    bits = jnp.asarray(np.random.default_rng(0).integers(0, 2, 96),
                       jnp.int32)
    bitpack(bits)
    snap = obs.REGISTRY.snapshot()
    assert snap["gauges"]["kernels.work.elements{op=bitpack}"] == 96.0
    assert snap["gauges"]["kernels.work.bits{op=bitpack}"] == 96.0


def test_trace_capture_writes_profile(tmp_path):
    from repro.obs.prof import start_trace, stop_trace, trace
    assert start_trace(None) is False
    assert stop_trace() is False             # nothing running
    with trace(tmp_path / "prof"):
        jnp.ones((8, 8)) @ jnp.ones((8, 8))
    assert stop_trace() is False             # context already stopped it
    assert any((tmp_path / "prof").rglob("*"))


def test_hlo_analysis_shim_back_compat():
    from repro.launch.hlo_analysis import analyze_hlo as shim
    from repro.obs.prof import analyze_hlo
    assert shim is analyze_hlo
    hlo = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((8, 8)), jnp.ones((8, 8))).compile().as_text()
    res = analyze_hlo(hlo)
    assert res["dot_flops_per_device"] == pytest.approx(2 * 8 ** 3)
