"""Range-analytics engine vs numpy oracles (np.sort / np.unique /
np.bincount on the decoded range).

Coverage per the acceptance criteria: uniform, skewed (Zipf) and all-equal
symbol distributions; σ ∈ {4, 256, 1000}; empty ranges and lo == hi;
single-matrix and sharded paths; a ≥1024-query mixed batch under one jit
trace; parallel (vmapped) shard builds bit-identical to the loop.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analytics import (ShardedAnalytics, build_sharded_analytics,
                             range_count, range_distinct, range_histogram,
                             range_quantile, range_topk, range_topk_greedy)
from repro.core import build_wavelet_matrix
from repro.data import build_compressed_corpus


def _texts(n: int, sigma: int, seed: int = 0):
    """The three acceptance distributions."""
    rng = np.random.default_rng(seed)
    return {
        "uniform": rng.integers(0, sigma, n).astype(np.uint32),
        "zipf": (rng.zipf(1.4, n) % sigma).astype(np.uint32),
        "all_equal": np.full(n, sigma - 1, np.uint32),
    }


def _ranges(n: int, num: int, rng):
    """Random query ranges incl. empty, lo == hi, full-span, end-hugging."""
    lo = rng.integers(0, n + 1, num).astype(np.int64)
    hi = rng.integers(0, n + 1, num).astype(np.int64)
    lo, hi = np.minimum(lo, hi), np.maximum(lo, hi)
    lo[0], hi[0] = 0, n          # full span
    lo[1], hi[1] = 5, 5          # empty (lo == hi)
    lo[2], hi[2] = n, n          # empty at the end
    if num > 3:
        lo[3], hi[3] = n - 1, n  # single element
    return lo, hi


# The ops under test, each one jitted call per query (the structure is an
# argument, so every text of a given shape reuses one executable).
_quantile = jax.jit(lambda w, a, b, k: range_quantile(w, a, b, k))
_count = jax.jit(lambda w, a, b, s0, s1: range_count(w, a, b, s0, s1))
_distinct = jax.jit(lambda w, a, b: range_distinct(w, a, b))
_topk = jax.jit(lambda w, a, b, k: range_topk(w, a, b, k),
                static_argnums=3)
_eng_quantile = jax.jit(lambda e, a, b, k: e.range_quantile(a, b, k))
_eng_count = jax.jit(lambda e, a, b, s0, s1: e.range_count(a, b, s0, s1))
_eng_distinct = jax.jit(lambda e, a, b: e.range_distinct(a, b))
_eng_topk = jax.jit(lambda e, a, b, k: e.range_topk(a, b, k),
                    static_argnums=3)


def _check_all_ops(seq, wm_ops, sigma, rng, tag, topk_k=6):
    """``wm_ops``: dict of callables mirroring the op signatures."""
    n = len(seq)
    lo, hi = _ranges(n, 12, rng)
    for i in range(len(lo)):
        sl = np.sort(seq[lo[i]:hi[i]])
        # quantile (k in-range, k clamped high, k=0)
        for k in (0, max(0, len(sl) // 2), len(sl) + 3):
            got = int(wm_ops["quantile"](lo[i], hi[i], k))
            want = -1 if len(sl) == 0 else sl[min(k, len(sl) - 1)]
            assert got == want, (tag, "quantile", lo[i], hi[i], k)
        # orthogonal count over random + degenerate symbol bands
        for sl_, sh_ in [(0, sigma), (sigma // 2, sigma // 2),
                         tuple(sorted(rng.integers(0, sigma + 3, 2)))]:
            got = int(wm_ops["count"](lo[i], hi[i], sl_, sh_))
            seg = seq[lo[i]:hi[i]]
            want = int(((seg >= sl_) & (seg < sh_)).sum())
            assert got == want, (tag, "count", lo[i], hi[i], sl_, sh_)
        # distinct
        got = int(wm_ops["distinct"](lo[i], hi[i]))
        assert got == len(np.unique(seq[lo[i]:hi[i]])), (tag, "distinct")
        # top-k: counts must match the oracle's sorted top-k multiset and
        # every reported (symbol, count) pair must be truthful
        syms, cnts = map(np.asarray, wm_ops["topk"](lo[i], hi[i], topk_k))
        bc = np.bincount(seq[lo[i]:hi[i]], minlength=sigma + 1)
        want_c = np.sort(bc[bc > 0])[::-1][:topk_k]
        valid = syms >= 0
        assert np.array_equal(cnts[valid], want_c), (tag, "topk", lo[i],
                                                     hi[i])
        assert (cnts[~valid] == 0).all(), (tag, "topk pad")
        for s, c in zip(syms[valid], cnts[valid]):
            assert bc[s] == c, (tag, "topk pair", s, c)


# ---------------------------------------------------------------------------
# single wavelet matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sigma", [4, 256, 1000])
def test_single_matrix_ops_match_numpy(sigma):
    n = 700
    for name, seq in _texts(n, sigma, seed=sigma).items():
        wm = build_wavelet_matrix(jnp.asarray(seq), sigma, sample_rate=128)
        rng = np.random.default_rng(sigma + 1)
        ops = {
            "quantile": lambda a, b, k: _quantile(wm, a, b, k),
            "count": lambda a, b, s0, s1: _count(wm, a, b, s0, s1),
            "distinct": lambda a, b: _distinct(wm, a, b),
            "topk": lambda a, b, k: _topk(wm, a, b, k),
        }
        _check_all_ops(seq, ops, sigma, rng, f"single/{name}/σ{sigma}")


def test_histogram_matches_bincount():
    n, sigma = 900, 97
    seq = _texts(n, sigma, seed=3)["zipf"]
    wm = build_wavelet_matrix(jnp.asarray(seq), sigma, sample_rate=128)
    for lo, hi in [(0, n), (100, 101), (50, 50), (123, 877)]:
        h = np.asarray(range_histogram(wm, lo, hi))
        want = np.bincount(seq[lo:hi], minlength=len(h))
        assert np.array_equal(h, want), (lo, hi)


def test_topk_greedy_exact_with_full_budget():
    """With a budget covering the whole tree the greedy walk is exact even
    on the adversarial uniform distribution."""
    n, sigma = 600, 37
    for name, seq in _texts(n, sigma, seed=7).items():
        wm = build_wavelet_matrix(jnp.asarray(seq), sigma, sample_rate=128)
        pow2 = 1 << wm.nbits
        syms, cnts = map(np.asarray,
                         range_topk_greedy(wm, 50, 550, 5, budget=2 * pow2))
        bc = np.bincount(seq[50:550], minlength=sigma)
        want_c = np.sort(bc[bc > 0])[::-1][:5]
        valid = syms >= 0
        assert np.array_equal(cnts[valid], want_c), name
        for s, c in zip(syms[valid], cnts[valid]):
            assert bc[s] == c, name


def test_topk_greedy_default_budget_on_skewed():
    """The default k·(logσ+1) pop budget is exact on Zipf-like traffic."""
    rng = np.random.default_rng(13)
    n, sigma = 1500, 256
    seq = (rng.zipf(1.6, n) % sigma).astype(np.uint32)
    wm = build_wavelet_matrix(jnp.asarray(seq), sigma, sample_rate=128)
    syms, cnts = map(np.asarray, range_topk_greedy(wm, 0, n, 4))
    bc = np.bincount(seq, minlength=sigma)
    want_c = np.sort(bc[bc > 0])[::-1][:4]
    valid = syms >= 0
    assert np.array_equal(cnts[valid], want_c)


def test_topk_greedy_pruned_matches_exact():
    """Lower-bound pruning (ceil(weight / leaves-below) per frontier node)
    never changes an exact answer: the pruned greedy path matches the
    exact histogram top-k on both zipf and uniform traffic, with and
    without the frontier pruning enabled."""
    from repro.analytics import range_topk
    rng = np.random.default_rng(21)
    n, sigma, k = 1200, 64, 5
    texts = {
        "zipf": (rng.zipf(1.5, n) % sigma).astype(np.uint32),
        "uniform": rng.integers(0, sigma, n).astype(np.uint32),
    }
    for name, seq in texts.items():
        wm = build_wavelet_matrix(jnp.asarray(seq), sigma, sample_rate=128)
        budget = None if name == "zipf" else 2 * (1 << wm.nbits)
        want_s, want_c = map(np.asarray, range_topk(wm, 100, 1100, k))
        got_s, got_c = map(np.asarray, range_topk_greedy(
            wm, 100, 1100, k, budget=budget, prune=True))
        assert np.array_equal(got_c, want_c), name
        bc = np.bincount(seq[100:1100], minlength=sigma)
        for s, c in zip(got_s[got_s >= 0], got_c[got_s >= 0]):
            assert bc[s] == c, name
        raw_s, raw_c = map(np.asarray, range_topk_greedy(
            wm, 100, 1100, k, budget=budget, prune=False))
        assert np.array_equal(raw_c, want_c), name


# ---------------------------------------------------------------------------
# sharded engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sigma", [4, 256, 1000])
def test_sharded_ops_match_numpy(sigma):
    n, sb = 2100, 9              # 5 shards of 512, cross-shard ranges
    for name, seq in _texts(n, sigma, seed=sigma + 5).items():
        eng = build_sharded_analytics(seq, sigma, shard_bits=sb,
                                      sample_rate=128)
        assert eng.num_shards == 5
        rng = np.random.default_rng(sigma + 2)
        ops = {
            "quantile": lambda a, b, k: _eng_quantile(eng, a, b, k),
            "count": lambda a, b, s0, s1: _eng_count(eng, a, b, s0, s1),
            "distinct": lambda a, b: _eng_distinct(eng, a, b),
            "topk": lambda a, b, k: _eng_topk(eng, a, b, k),
        }
        _check_all_ops(seq, ops, sigma, rng, f"sharded/{name}/σ{sigma}")


def test_sharded_greedy_topk_is_global():
    """The greedy frontier weighs nodes by the summed width across shards:
    a symbol frequent only via many shards still wins."""
    n, sigma, sb = 2048, 16, 9
    rng = np.random.default_rng(21)
    seq = (rng.zipf(1.5, n) % sigma).astype(np.uint32)
    eng = build_sharded_analytics(seq, sigma, shard_bits=sb,
                                  sample_rate=128)
    syms, cnts = map(np.asarray,
                     eng.range_topk_greedy(100, 1900, 3, budget=64))
    bc = np.bincount(seq[100:1900], minlength=sigma)
    want_c = np.sort(bc[bc > 0])[::-1][:3]
    assert np.array_equal(cnts[syms >= 0], want_c)


def test_engine_adopts_corpus_shards():
    """ShardedAnalytics.from_corpus shares the CompressedCorpus pytree and
    the corpus's own analytics methods agree with the engine's."""
    n, sigma = 1500, 64
    seq = _texts(n, sigma, seed=9)["zipf"]
    corpus = build_compressed_corpus(seq, sigma, shard_bits=9)
    eng = ShardedAnalytics.from_corpus(corpus)
    assert eng.num_shards == corpus.num_shards
    lo, hi, k = 37, 1402, 200
    assert int(eng.range_quantile(lo, hi, k)) == np.sort(seq[lo:hi])[k]
    assert int(corpus.range_quantile(lo, hi, k)) == np.sort(seq[lo:hi])[k]
    assert (int(corpus.range_distinct(lo, hi))
            == len(np.unique(seq[lo:hi])))
    s, c = corpus.range_topk(lo, hi, 3)
    bc = np.bincount(seq[lo:hi], minlength=sigma)
    assert np.array_equal(np.asarray(c), np.sort(bc[bc > 0])[::-1][:3])


# ---------------------------------------------------------------------------
# batched serving: ≥1024 mixed queries, one jit trace
# ---------------------------------------------------------------------------

def test_batch_1024_mixed_queries_single_trace():
    n, sigma, sb, B = 4096, 64, 10, 1024
    seq = _texts(n, sigma, seed=17)["zipf"]
    eng = build_sharded_analytics(seq, sigma, shard_bits=sb,
                                  sample_rate=128)
    traces = []

    def serve(e, lo, hi, k, s0, s1):
        traces.append(1)
        return (e.range_quantile(lo, hi, k),
                e.range_count(lo, hi, s0, s1),
                e.range_topk(lo, hi, 4),
                e.range_distinct(lo, hi))

    f = jax.jit(serve)
    rng = np.random.default_rng(23)

    def batch(seed):
        r = np.random.default_rng(seed)
        lo = r.integers(0, n, B).astype(np.int32)
        hi = np.minimum(lo + r.integers(1, n // 2, B), n).astype(np.int32)
        k = r.integers(0, n, B).astype(np.int32)
        s0 = r.integers(0, sigma, B).astype(np.int32)
        return (jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(k),
                jnp.asarray(s0), jnp.asarray(np.minimum(s0 + 7, sigma)))

    a1 = f(eng, *batch(1))
    a2 = f(eng, *batch(2))            # new values, same shapes
    jax.block_until_ready(a2)
    assert len(traces) == 1, "batched serving retraced per call"

    # spot-verify the second batch against numpy
    lo, hi, k, s0, s1 = [np.asarray(x) for x in batch(2)]
    quant, cnt, (tsyms, tcnts), dist = [np.asarray(x) if not isinstance(x, tuple)
                                        else x for x in a2]
    tsyms, tcnts = np.asarray(tsyms), np.asarray(tcnts)
    for i in rng.integers(0, B, 32):
        sl = seq[lo[i]:hi[i]]
        ss = np.sort(sl)
        assert quant[i] == (ss[min(k[i], len(ss) - 1)] if len(ss) else -1)
        assert cnt[i] == ((sl >= s0[i]) & (sl < s1[i])).sum()
        assert dist[i] == len(np.unique(sl))
        bc = np.bincount(sl, minlength=sigma)
        assert np.array_equal(tcnts[i][tsyms[i] >= 0],
                              np.sort(bc[bc > 0])[::-1][:4])


# ---------------------------------------------------------------------------
# parallel shard builds
# ---------------------------------------------------------------------------

def test_parallel_shard_build_identical_to_loop():
    n, sigma = 3000, 128
    seq = _texts(n, sigma, seed=31)["uniform"]
    loop = build_compressed_corpus(seq, sigma, shard_bits=9, parallel=False)
    traced = build_compressed_corpus(seq, sigma, shard_bits=9, parallel=True)
    for a, b in zip(jax.tree.leaves(loop.shards),
                    jax.tree.leaves(traced.shards)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))


def test_parallel_fm_shard_build_identical_to_loop():
    from repro.index import build_sharded_index
    rng = np.random.default_rng(33)
    toks = rng.integers(0, 32, 1200).astype(np.int64)
    loop = build_sharded_index(toks, 32, shard_bits=9, sample_rate=16,
                               parallel=False)
    traced = build_sharded_index(toks, 32, shard_bits=9, sample_rate=16,
                                 parallel=True)
    for a, b in zip(jax.tree.leaves(loop.shards),
                    jax.tree.leaves(traced.shards)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
