"""Full-text index subsystem: suffix array, BWT, FM-index, sharded index.

All oracles are pure numpy (sorted-suffix comparison, sliding-window
substring match) — no hypothesis required.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.wavelet_matrix import (wm_access_descend, wm_block_starts,
                                       wm_descend, wm_rank)
from repro.data import make_corpus
from repro.index import (build_fm_index, build_sharded_index, bwt_decode,
                         bwt_encode, fm_count, fm_locate, suffix_array,
                         suffix_array_naive)
from repro.index.fm_index import _lf_base


def _naive_count(text: np.ndarray, pat: np.ndarray, plen: int) -> int:
    if plen > len(text) or plen == 0:
        return 0
    win = np.lib.stride_tricks.sliding_window_view(text, plen)
    return int((win == pat[:plen]).all(axis=1).sum())


def _texts(n: int, sigma: int, seed: int = 0):
    """The three acceptance distributions + adversarial extras."""
    rng = np.random.default_rng(seed)
    zipf = rng.zipf(1.3, n) % sigma
    return {
        "uniform": rng.integers(0, sigma, n).astype(np.int64),
        "skewed": zipf.astype(np.int64),
        "periodic": (np.arange(n) % min(sigma, 7)).astype(np.int64),
        "all_equal": np.full(n, sigma - 1, np.int64),
    }


# ---------------------------------------------------------------------------
# suffix array + BWT
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,sigma", [(1, 2), (2, 2), (13, 4), (100, 4),
                                     (257, 256), (120, 1000)])
def test_suffix_array_matches_naive(n, sigma):
    rng = np.random.default_rng(n * 1000 + sigma)
    for name, seq in _texts(n, sigma, seed=n).items():
        got = np.asarray(suffix_array(jnp.asarray(seq, jnp.int32)))
        assert np.array_equal(got, suffix_array_naive(seq)), (name, n, sigma)
    seq = rng.integers(0, sigma, n)
    assert np.array_equal(np.asarray(suffix_array(jnp.asarray(seq))),
                          suffix_array_naive(seq))


def test_suffix_array_backends_agree():
    rng = np.random.default_rng(7)
    seq = jnp.asarray(rng.integers(0, 16, 300), jnp.int32)
    a = np.asarray(suffix_array(seq, backend="counting"))
    b = np.asarray(suffix_array(seq, backend="xla"))
    assert np.array_equal(a, b)


def test_bwt_roundtrip():
    rng = np.random.default_rng(1)
    for n, sigma in [(1, 2), (50, 3), (400, 256)]:
        seq = rng.integers(0, sigma, n).astype(np.int64)
        bwt, sa, C = bwt_encode(jnp.asarray(seq), sigma)
        assert bwt.shape[0] == n + 1
        assert int(C[-1]) == n + 1
        assert np.array_equal(np.asarray(bwt_decode(bwt, C)), seq)


# ---------------------------------------------------------------------------
# FM-index count/locate vs naive numpy — acceptance distributions & sigmas
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sigma", [4, 256, 1000])
def test_fm_count_matches_naive(sigma):
    n, L, B = 300, 6, 24
    for t_i, (name, text) in enumerate(_texts(n, sigma, seed=sigma).items()):
        fm = build_fm_index(jnp.asarray(text, jnp.int32), sigma,
                            sample_rate=16)
        rng = np.random.default_rng(sigma * 10 + t_i)
        pats = np.full((B, L), sigma, np.int32)
        lens = rng.integers(1, L + 1, B).astype(np.int32)
        for i in range(B):
            if i % 3 == 0:   # random pattern — usually a miss
                pats[i, :lens[i]] = rng.integers(0, sigma, lens[i])
            else:            # substring — guaranteed hit
                s = int(rng.integers(0, n - lens[i]))
                pats[i, :lens[i]] = text[s:s + lens[i]]
        got = np.asarray(fm_count(fm, jnp.asarray(pats), jnp.asarray(lens)))
        want = np.array([_naive_count(text, p, int(l))
                         for p, l in zip(pats, lens)])
        assert np.array_equal(got, want), (name, sigma)


def test_fm_count_batch64_under_jit_pytree():
    """Acceptance: ≥64-pattern vmapped batch under jax.jit with the index
    crossing the jit boundary as a pytree argument."""
    n, sigma, L, B = 1024, 256, 8, 64
    rng = np.random.default_rng(0)
    text = rng.integers(0, sigma, n).astype(np.int64)
    fm = build_fm_index(jnp.asarray(text, jnp.int32), sigma)
    pats = np.full((B, L), sigma, np.int32)
    lens = rng.integers(1, L + 1, B).astype(np.int32)
    for i in range(B):
        s = int(rng.integers(0, n - lens[i]))
        pats[i, :lens[i]] = text[s:s + lens[i]]
    f = jax.jit(lambda ix, p, l: ix.count(p, l))
    got = np.asarray(f(fm, jnp.asarray(pats), jnp.asarray(lens)))
    want = np.array([_naive_count(text, p, int(l))
                     for p, l in zip(pats, lens)])
    assert np.array_equal(got, want)
    assert (want >= 1).all()          # every pattern was a real substring


def test_fm_locate_exact_and_subset():
    n = 400
    rng = np.random.default_rng(3)
    for sigma in (8, 4, 256):      # 3 and 9 levels of LF descent
        text = rng.integers(0, sigma, n).astype(np.int64)
        fm = build_fm_index(jnp.asarray(text, jnp.int32), sigma,
                            sample_rate=8)
        for plen in (1, 2, 4):
            s = int(rng.integers(0, n - plen))
            pat = text[s:s + plen].astype(np.int32)
            ref = [i for i in range(n - plen + 1)
                   if np.array_equal(text[i:i + plen], pat)]
            got = np.asarray(fm_locate(fm, jnp.asarray(pat),
                                       jnp.int32(plen), max_hits=64))
            hits = [int(x) for x in got if x >= 0]
            if len(ref) <= 64:
                assert hits == ref, (sigma, plen)  # all matches, in order
            else:
                assert len(hits) == 64 and set(hits) <= set(ref), (
                    sigma, plen)


def test_fm_locate_adversarial_texts():
    sigma = 4
    for name, text in _texts(200, sigma, seed=5).items():
        fm = build_fm_index(jnp.asarray(text, jnp.int32), sigma,
                            sample_rate=16)
        pat = text[:3].astype(np.int32)
        ref = [i for i in range(198) if np.array_equal(text[i:i + 3], pat)]
        got = np.asarray(fm_locate(fm, jnp.asarray(pat), jnp.int32(3),
                                   max_hits=256))
        hits = [int(x) for x in got if x >= 0]
        assert hits == ref, name


# ---------------------------------------------------------------------------
# LF as one descent: base table from C
# ---------------------------------------------------------------------------

def _lf_texts(sigma: int):
    rng = np.random.default_rng(sigma + 1)
    return {"random": rng.integers(0, sigma, 300),
            "one_symbol": np.full(64, sigma // 2),
            "each_once": rng.permutation(sigma)}


# σ = 7: the out-of-alphabet code σ+1 = 8 needs a 4th bit the 3 levels do
# not have, so the descent reads it as code 0 (as wm_rank does)
_LF_CASES = [(sigma, kind) for sigma in (4, 7, 256)
             for kind in ("random", "one_symbol", "each_once")]


@pytest.mark.parametrize("sigma,kind", _LF_CASES)
def test_block_starts_from_C_equal_descent_from_zero(sigma, kind):
    """Every index of C, absent codes and σ+1 included."""
    text = _lf_texts(sigma)[kind]
    fm = build_fm_index(jnp.asarray(text, jnp.int32), sigma, sample_rate=8)
    k = fm.C.shape[0]
    assert k == sigma + 2
    counts = jnp.diff(fm.C, append=fm.C[-1:])
    starts = np.asarray(wm_block_starts(counts, fm.wm.nbits))
    codes = jnp.arange(k, dtype=jnp.int32)
    from_zero = np.asarray(wm_descend(fm.wm, codes, jnp.zeros_like(codes)))
    assert np.array_equal(starts, from_zero)


@pytest.mark.parametrize("sigma,kind", _LF_CASES)
def test_lf_base_plus_descent_equals_C_plus_rank(sigma, kind):
    """base[c] + descend(c, i) = C[c] + rank(c, i) at random i in [0, m],
    and an access descent ends where the descent along its symbol does."""
    text = _lf_texts(sigma)[kind]
    fm = build_fm_index(jnp.asarray(text, jnp.int32), sigma, sample_rate=8)
    rng = np.random.default_rng(sigma * 7 + len(text))
    k = fm.C.shape[0]
    c = jnp.asarray(np.repeat(np.arange(k), 8), jnp.int32)
    i = jnp.asarray(rng.integers(0, fm.m + 1, c.shape[0]), jnp.int32)
    got = _lf_base(fm)[c] + wm_descend(fm.wm, c, i)
    want = fm.C[c] + wm_rank(fm.wm, c, i)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    j = jnp.arange(fm.m, dtype=jnp.int32)
    sym, end = wm_access_descend(fm.wm, j)
    assert np.array_equal(np.asarray(end),
                          np.asarray(wm_descend(fm.wm, sym, j)))


@pytest.mark.parametrize("sigma", [4, 256])
def test_fm_count_fires_backward_step_counter(sigma):
    """One trace of backward search advertises 2·nbits rank1 per step
    (one descent per range end); a second call of the jitted count hits
    the cache and fires nothing."""
    text = np.arange(200) % sigma
    fm = build_fm_index(jnp.asarray(text, jnp.int32), sigma, sample_rate=8)
    pats = jnp.asarray(text[:12].reshape(3, 4), jnp.int32)
    lens = jnp.asarray([4, 2, 1], jnp.int32)
    obs.REGISTRY.reset()
    f = jax.jit(fm_count)
    jax.block_until_ready(f(fm, pats, lens))
    jax.block_until_ready(f(fm, pats, lens))
    snap = {k: v for k, v in obs.REGISTRY.snapshot()["counters"].items()
            if k.startswith("index.backward_step")}
    nbits = fm.wm.nbits
    assert snap == {
        f"index.backward_step{{rank1_per_step={2 * nbits}}}": 1}


# ---------------------------------------------------------------------------
# sharded index
# ---------------------------------------------------------------------------

def test_sharded_count_matches_global_naive():
    """Seam stitching makes ``count`` exact against the *global* sliding
    oracle — matches crossing shard boundaries included."""
    n, sigma, sb = 2500, 64, 9          # 5 shards of 512, last one padded
    toks = np.asarray(make_corpus(n, sigma, seed=2), np.int64)
    idx = build_sharded_index(toks, sigma, shard_bits=sb, sample_rate=16)
    assert idx.num_shards == 5
    rng = np.random.default_rng(4)
    B, L = 16, 5
    pats = np.full((B, L), sigma, np.int32)
    lens = rng.integers(1, L + 1, B).astype(np.int32)
    for i in range(B):
        s = int(rng.integers(0, n - lens[i]))
        pats[i, :lens[i]] = toks[s:s + lens[i]]
    got = np.asarray(idx.count(jnp.asarray(pats), jnp.asarray(lens)))
    want = np.array([_naive_count(toks, p, int(l))
                     for p, l in zip(pats, lens)])
    assert np.array_equal(got, want)
    # per-shard decomposition still reports within-shard matches only
    S = idx.shard_size
    by_shard = np.asarray(idx.count_by_shard(jnp.asarray(pats),
                                             jnp.asarray(lens)))
    assert by_shard.shape == (5, B)
    want_within = np.array([sum(_naive_count(toks[s0:s0 + S], p, int(l))
                                for s0 in range(0, n, S))
                            for p, l in zip(pats, lens)])
    assert np.array_equal(by_shard.sum(axis=0), want_within)


def test_sharded_count_stitches_planted_seam_matches():
    """Patterns planted *across* every shard seam are found by count."""
    n, sigma, sb = 2048, 32, 9
    rng = np.random.default_rng(11)
    toks = rng.integers(0, sigma, n).astype(np.int64)
    S = 1 << sb
    planted = np.array([9, 4, 9, 4, 9, 4], np.int64)
    for p in range(S, n, S):            # straddle every internal boundary
        toks[p - 3:p + 3] = planted
    idx = build_sharded_index(toks, sigma, shard_bits=sb, sample_rate=16)
    pats_np = np.full((2, 6), sigma, np.int64)
    pats_np[0] = planted
    pats_np[1, :4] = planted[:4]
    pats = jnp.asarray(pats_np, jnp.int32)
    lens = jnp.asarray([6, 4], jnp.int32)
    got = np.asarray(idx.count(pats, lens))
    for i, l in enumerate([6, 4]):
        assert got[i] == _naive_count(toks, np.asarray(pats[i]), l), i
    # seam contribution alone equals global minus within-shard
    by_shard = np.asarray(idx.count_by_shard(pats, lens)).sum(axis=0)
    assert (got - by_shard >= idx.num_shards - 1).all()

    # overlap 0 disables stitching → within-shard counts only
    idx0 = build_sharded_index(toks, sigma, shard_bits=sb, sample_rate=16,
                               seam_overlap=0)
    got0 = np.asarray(idx0.count(pats, lens))
    assert np.array_equal(got0, by_shard)


def test_sharded_locate_positions_are_real_matches():
    n, sigma, sb = 1200, 16, 9
    rng = np.random.default_rng(6)
    toks = rng.integers(0, sigma, n).astype(np.int64)
    idx = build_sharded_index(toks, sigma, shard_bits=sb, sample_rate=16)
    pats = np.full((4, 3), sigma, np.int32)
    for i in range(4):
        s = int(rng.integers(0, n - 3))
        pats[i] = toks[s:s + 3]
    lens = np.full(4, 3, np.int32)
    pos = np.asarray(idx.locate(jnp.asarray(pats), jnp.asarray(lens),
                                max_hits_per_shard=8))
    for i in range(4):
        hits = [int(x) for x in pos[i] if x >= 0]
        assert hits, i                        # sampled from corpus → ≥1 hit
        assert hits == sorted(hits)
        for p0 in hits:
            assert np.array_equal(toks[p0:p0 + 3], pats[i]), (i, p0)


def test_sharded_pad_symbol_never_matches_padding():
    """Out-of-vocab query symbols (σ included — the tail-shard pad value)
    must count 0 and locate nothing, not the padding run. The edges of
    backward search against naive counts on the raw text: zero-length
    patterns, lengths 1 and seam_overlap + 1, and ranges that empty at
    the first step (out-of-vocab or absent last symbol)."""
    sigma = 7
    toks = np.arange(100) % sigma
    toks[40] = 5                        # "5 5" occurs once, "2 2" never
    idx = build_sharded_index(toks, sigma, shard_bits=6, sample_rate=8)
    assert idx.num_shards == 2          # 36 tokens + 28 padding in shard 1
    pats = jnp.asarray([[sigma, 0], [sigma + 3, 0], [-1, 0]], jnp.int32)
    lens = jnp.asarray([1, 2, 1], jnp.int32)
    assert np.asarray(idx.count(pats, lens)).tolist() == [0, 0, 0]
    pos = np.asarray(idx.locate(pats, lens, max_hits_per_shard=4))
    assert (pos == -1).all()

    L = idx.seam_overlap + 1
    rows, plens = [], []

    def add(p, plen):
        rows.append(list(p) + [sigma] * (L - len(p)))
        plens.append(plen)
    add([], 0)                          # zero-length: counts 0
    add([3, 4], 0)                      # zero length, symbols past it
    for c in range(sigma):
        add([c], 1)
    add(toks[56:56 + L], L)             # crosses the seam at 64
    add(toks[2:2 + L], L)               # within shard 0
    add(toks[70:70 + L], L)             # within the padded shard
    add([1, 2, sigma], 3)               # out-of-vocab last symbol
    add([1, 2, sigma + 9], 3)
    add([3, -5], 2)
    add([2, 2], 2)                      # absent at the first step
    add([1, 0], 2)                      # absent at the second step
    add([5, 5], 2)
    add([sigma, 0, 1], 3)               # out-of-vocab first symbol
    got = np.asarray(idx.count(jnp.asarray(rows, jnp.int32),
                               jnp.asarray(plens, jnp.int32)))
    want = [_naive_count(toks, np.asarray(r), l)
            for r, l in zip(rows, plens)]
    assert got.tolist() == want
    assert want[2:2 + sigma] == np.bincount(toks, minlength=sigma).tolist()
    assert min(want[2 + sigma:5 + sigma]) >= 1


def test_sharded_tiny_and_padded_shard():
    """Length-1 corpus and a shard that is almost entirely padding."""
    sigma = 8
    idx = build_sharded_index(np.array([3]), sigma, shard_bits=6,
                              sample_rate=4)
    assert idx.num_shards == 1
    got = np.asarray(idx.count(jnp.asarray([[3], [5]], jnp.int32),
                               jnp.asarray([1, 1], jnp.int32)))
    assert got.tolist() == [1, 0]
    pos = np.asarray(idx.locate(jnp.asarray([[3]], jnp.int32),
                                jnp.asarray([1], jnp.int32), 4))
    assert [int(x) for x in pos[0] if x >= 0] == [0]

    # shard boundary: 513 tokens over 512-sized shards → 2nd shard has 1
    toks = np.arange(513) % sigma
    idx2 = build_sharded_index(toks, sigma, shard_bits=9, sample_rate=16)
    assert idx2.num_shards == 2
    got = np.asarray(idx2.count(jnp.asarray([[513 % 8]], jnp.int32),
                                jnp.asarray([1], jnp.int32)))
    want = int((toks == 513 % 8).sum())
    assert int(got[0]) == want
