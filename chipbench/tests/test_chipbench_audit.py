"""Whole runs of the query cell at CPU sizes: a sound run is correct; the
control and each fault planted in the timed path come out not correct."""
import pytest

from chipbench import control

from .tiny import CONFIGS, run_tiny

AUDIT = "pile_vocab17.audit"


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench-cache")


def test_audit_sound_run_is_correct(cache_dir):
    out = run_tiny(AUDIT, cache_dir)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["query_p95_ms"]["value"] > 0


def test_audit_latency_is_timed_by_the_benchmark(cache_dir, monkeypatch):
    """Every pump stalls 0.3 s while the program reports each answer's
    latency as 0: the p95 still holds the stall."""
    import dataclasses
    import time

    from repro.serving.admission import Ticket
    from repro.serving.frontend import QueryFrontend
    pump, resolve = QueryFrontend.pump, Ticket.resolve

    def slow_pump(self):
        if self.queue.depth:
            time.sleep(0.3)
        return pump(self)

    monkeypatch.setattr(QueryFrontend, "pump", slow_pump)
    monkeypatch.setattr(Ticket, "resolve", lambda self, a: resolve(
        self, dataclasses.replace(a, latency_s=0.0)))
    out = run_tiny(AUDIT, cache_dir)
    assert out["correct"], out["checks"]
    assert out["metrics"]["query_p95_ms"]["value"] >= 300.0


def test_audit_answer_altered_is_caught(cache_dir, monkeypatch):
    from repro.analytics import engine
    orig = engine.sharded_range_count
    monkeypatch.setattr(engine, "sharded_range_count",
                        lambda *a, **k: orig(*a, **k) + 1)
    out = run_tiny(AUDIT, cache_dir)
    assert not out["correct"]
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_audit_half_the_batch_left_out_is_caught(cache_dir, monkeypatch):
    from repro.serving.frontend import QueryFrontend
    orig = QueryFrontend._resolve

    def resolve_half(self, batch, *args):
        return orig(self, batch[:len(batch) // 2], *args)
    monkeypatch.setattr(QueryFrontend, "_resolve", resolve_half)
    out = run_tiny(AUDIT, cache_dir)
    assert not out["correct"]
    assert out["checks"]["never_answered"]["value"] > 0


def test_audit_control_is_caught(cache_dir):
    out = run_tiny(AUDIT, cache_dir, hooks=control.hooks(
        "range_audit", CONFIGS["pile_vocab17"]), seconds=3.0)
    assert not out["correct"]
    assert out["checks"]["wrong_answers"]["value"] > 0
