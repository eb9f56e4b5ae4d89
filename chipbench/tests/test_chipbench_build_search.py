"""Whole runs of the construction and search cells at CPU sizes: a sound
run is correct; the control and each fault planted in the timed path
come out not correct."""
import jax
import pytest

from chipbench import control
from chipbench.kinds import bulk_build

from .tiny import CONFIGS, TRAFFIC, run_tiny

INGEST, SEARCH = "pile_vocab17.ingest", "pizzachili_bytes.search"


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench-cache")


def _program_build():
    return bulk_build.program_build(CONFIGS["pile_vocab17"])


def _flip_one_bit(tokens):
    store = _program_build()(tokens)
    words = store.shards.bitvectors.rank.words
    return jax.tree.map(
        lambda l: l.at[0, 0, 0].set(l[0, 0, 0] ^ 1) if l is words else l,
        store)


def _half_the_shards(tokens):
    store = _program_build()(tokens)
    return jax.tree.map(
        lambda l: l.at[l.shape[0] // 2:].set(0) if l.ndim > 1 else l, store)


def test_ingest_sound_run_is_correct(cache_dir):
    out = run_tiny(INGEST, cache_dir)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1
    assert out["metrics"]["build_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("build", [_flip_one_bit, _half_the_shards],
                         ids=["bit_altered", "half_left_out"])
def test_ingest_fault_is_caught(cache_dir, build):
    out = run_tiny(INGEST, cache_dir, hooks={"build": build})
    assert not out["correct"]
    assert out["checks"]["wrong_leaf_elements"]["value"] > 0


def test_ingest_control_is_caught(cache_dir):
    out = run_tiny(INGEST, cache_dir,
                   hooks=control.hooks("bulk_build", CONFIGS["pile_vocab17"]))
    assert not out["correct"]


def test_search_sound_run_is_correct(cache_dir):
    out = run_tiny(SEARCH, cache_dir)
    assert out["correct"], out["checks"]
    assert out["metrics"]["search_patterns_per_s"]["value"] > 0


def _count_plus_one(orig):
    return lambda self, p, ln: orig(self, p, ln) + 1


def _count_half(orig):
    def count(self, p, ln):
        out = orig(self, p, ln)
        return out.at[out.shape[0] // 2:].set(0)
    return count


@pytest.mark.parametrize("fault", [_count_plus_one, _count_half],
                         ids=["answer_altered", "half_left_out"])
def test_search_fault_is_caught(cache_dir, monkeypatch, fault):
    from repro.index.sharded import ShardedTextIndex
    monkeypatch.setattr(ShardedTextIndex, "count",
                        fault(ShardedTextIndex.count))
    jax.clear_caches()              # the sound run traced the count already
    out = run_tiny(SEARCH, cache_dir)
    jax.clear_caches()
    assert not out["correct"]


def test_search_control_is_caught(cache_dir):
    out = run_tiny(SEARCH, cache_dir, hooks=control.hooks(
        "pattern_search", CONFIGS["pizzachili_bytes"]))
    assert not out["correct"]
    assert out["checks"]["wrong_counts"]["value"] > 0
    assert TRAFFIC[SEARCH]["max_len"] <= \
        CONFIGS["pizzachili_bytes"]["seam_overlap"] + 1
