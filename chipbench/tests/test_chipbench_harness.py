"""Fast CPU checks of the benchmark harness: the registry, the seeded
generators, the metric arithmetic, the device check and the trace
reduction (on a small trace recorded on a TPU v5e)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import data, reference, run, trace
from chipbench.kinds import bulk_build, range_audit

BENCH = run.load_bench()
TESTDATA = Path(__file__).resolve().parent.parent / "testdata"


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_registry_finds_every_file_of_a_cell(cell):
    c = run.find_cell(BENCH, cell)
    config = run.load_config(c["config"])
    assert config["name"] == c["config"]
    kind = run.load_kind(run.load_traffic(c["traffic"])["kind"])
    for fn in ("setup", "window", "metrics", "check"):
        assert callable(getattr(kind, fn))
    e2e, layer = run.cell_metrics(BENCH, cell)
    names = [m["name"] for m in e2e]
    assert "setup_s" in names and len(names) >= 2
    assert layer
    for m in layer:
        assert callable(run.load_metric(m["name"]))


def test_config_entries_match_their_files():
    for entry in BENCH["configs"]:
        cfg = json.loads((run.ROOT / entry["file"]).read_text())
        assert cfg["name"] == entry["name"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        assert cfg["source"] == entry["source"]


def test_zipf_tokens_follow_the_seed_past_32_bits():
    a = data.zipf_tokens(2 ** 40 + 3, 1, 4096, 512, 1.1, 64, 0)
    b = data.zipf_tokens(2 ** 40 + 3, 1, 4096, 512, 1.1, 64, 0)
    c = data.zipf_tokens(3, 1, 4096, 512, 1.1, 64, 0)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.uint32 and a.max() < 512
    assert np.all(a[63::64] == 0)


def test_request_schedule_and_patterns_follow_the_seed():
    tr = run.load_traffic("audit")
    s1 = range_audit.schedule(2 ** 33 + 1, tr, 1 << 20, 1 << 17, 5.0)
    s2 = range_audit.schedule(2 ** 33 + 1, tr, 1 << 20, 1 << 17, 5.0)
    s3 = range_audit.schedule(1, tr, 1 << 20, 1 << 17, 5.0)
    for k in s1:
        assert np.array_equal(s1[k], s2[k])
    assert not np.array_equal(s1["lo"][:10], s3["lo"][:10])
    assert np.all(s1["due"] < 5.0) and np.all(s1["b"] > s1["a"])
    assert np.all((s1["k"] >= 0) & (s1["k"] < s1["hi"] - s1["lo"]))
    text = data.zipf_tokens(5, 0, 1 << 12, 256, 1.1, 0, 0)
    p1 = data.pattern_pool(data.rng(7, 4), text, 256, 64, 0.75, 4, 16)
    p2 = data.pattern_pool(data.rng(7, 4), text, 256, 64, 0.75, 4, 16)
    assert all(np.array_equal(x, y) for x, y in zip(p1, p2))


def test_seam_patterns_cross_a_seam():
    text = data.zipf_tokens(5, 0, 1 << 14, 256, 1.1, 0, 0)
    pats, lens = data.pattern_pool(data.rng(7, 4), text, 256, 512, 1.0, 4,
                                   16, 1.0, 1 << 10)
    assert reference.crosses_seam(text, pats, lens, 1 << 10).all()
    pats, lens = data.pattern_pool(data.rng(8, 4), text, 256, 512, 0.75, 4,
                                   16, 0.125, 1 << 10)
    share = reference.crosses_seam(text, pats, lens, 1 << 10).mean()
    assert 0.08 < share < 0.3


def test_p95_counts_misses_at_infinity():
    lat = np.arange(1.0, 21.0)                  # 20 requests, 1..20 s
    assert range_audit.p95_ms(lat, 99.0) == 19000.0
    lat[[3, 7]] = np.inf                        # two misses: p95 is a miss
    assert range_audit.p95_ms(lat, 99.0) == 99000.0


def test_build_rate_counts_whole_builds_over_the_window():
    st = bulk_build.State({}, [], 1 << 22, None, 24.0)
    out = bulk_build.metrics(st, {"tokens": 3 << 22, "elapsed_s": 4.0})
    assert out["build_tokens_per_s"] == 3 * (1 << 22) / 4.0


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload",
         "pile_vocab17.ingest", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=run.ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode == 2
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def _union_ns(intervals, lo, hi):
    covered, end = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            covered += e - s
            end = e
    return covered


def test_trace_reduction_on_a_recorded_v5e_trace():
    tr = trace.load(str(TESTDATA / "tiny.xplane.pb"))
    lo, hi = trace.window(tr)
    out = trace.reduce(tr)
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    ops = list(tr.ops.values())
    assert len(ops) == 1
    _, st, en = ops[0]
    want = _union_ns(list(zip(st, en)), lo, hi) / 1e9
    assert out["busy_s"] == pytest.approx(want, rel=1e-9)
    assert 0 < out["busy_s"] < out["window_s"]
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "chipbench.host_wait"
    assert gaps[0][1] >= 0.02 * 0.9
    names = [n for n, _ in out["breakdown"]["device_ops"]]
    assert names and len(names) <= 10
    tiny = trace.program_ns(tr, lo, hi, lambda n: "chipbench_tiny" in n)
    assert 0 < tiny / 1e9 <= out["busy_s"] * 1.01
