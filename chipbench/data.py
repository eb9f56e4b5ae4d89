"""Inputs made from a seed: Zipf token streams, range queries, patterns.

Copied in spirit from the program's generators (``repro.data.synthetic``,
``chip_smoke.make_range_queries``, ``repro.index.sample_patterns``) so
that a change to the program cannot change the yardstick. The token
stream is drawn on the device in one jitted call (inverse CDF on 32-bit
integer thresholds), then read back once for the references.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, *salt: int) -> np.random.Generator:
    """numpy generator for ``seed`` (any whole number, negative or past
    64 bits) and a salt naming what it draws."""
    return np.random.default_rng([int(seed) % (1 << 64), *salt])


def _device_key(seed: int, salt: int):
    import jax
    word = np.random.SeedSequence([int(seed) % (1 << 64), salt])
    return jax.random.key(int(word.generate_state(1, np.uint32)[0]))


def zipf_cdf_u32(sigma: int, exponent: float) -> np.ndarray:
    """Inclusive CDF of Zipf(exponent) over ranks 1..sigma, scaled to
    uint32 thresholds: a uniform 32-bit word w draws rank
    searchsorted(cdf, w, 'right')."""
    p = np.arange(1, sigma + 1, dtype=np.float64) ** (-exponent)
    cdf = np.cumsum(p / p.sum())
    return np.minimum(np.floor(cdf * 2.0 ** 32), 2.0 ** 32 - 1).astype(
        np.uint32)


def zipf_tokens(seed: int, salt: int, n: int, sigma: int, exponent: float,
                doc_len: int, eos_id: int) -> np.ndarray:
    """(n,) uint32 Zipf(exponent) tokens; ids by shuffled rank, so
    frequency is not correlated with id; ``eos_id`` closes every
    ``doc_len``-token document (``doc_len`` 0: no documents)."""
    import jax
    import jax.numpy as jnp
    ids = rng(seed, salt).permutation(sigma).astype(np.uint32)
    cdf = zipf_cdf_u32(sigma, exponent)

    @jax.jit
    def draw(key, cdf, ids):
        words = jax.random.bits(key, (n,), jnp.uint32)
        rank = jnp.searchsorted(cdf, words, side="right", method="sort")
        toks = ids[jnp.minimum(rank, sigma - 1)]
        if not doc_len:
            return toks
        pos = jnp.arange(n, dtype=jnp.int32)
        return jnp.where(pos % doc_len == doc_len - 1, jnp.uint32(eos_id),
                         toks)

    return np.asarray(draw(_device_key(seed, salt), cdf, ids))


def range_queries(g: np.random.Generator, n: int, num: int,
                  narrow_share: float, narrow_max: int):
    """(lo, hi) position ranges: ``narrow_share`` of them 1..narrow_max
    wide, the rest up to the whole corpus — the data-audit mix."""
    lo = g.integers(0, n - 1, num)
    narrow = g.random(num) < narrow_share
    width = np.where(narrow, g.integers(1, narrow_max + 1, num),
                     g.integers(1, n + 1, num))
    hi = np.minimum(lo + width, n)
    return lo.astype(np.int64), hi.astype(np.int64)


def pattern_pool(g: np.random.Generator, text: np.ndarray, sigma: int,
                 num: int, substring_share: float, min_len: int,
                 max_len: int, seam_share: float = 0.0, seam_size: int = 0):
    """(num, max_len) int32 patterns padded with ``sigma`` and (num,)
    lengths: ``substring_share`` of them substrings of ``text``, the rest
    uniform over the alphabet (mostly misses). Of the substrings,
    ``seam_share`` of the pool are cut across a boundary between shards
    of ``seam_size`` symbols (1 to length - 1 of their symbols before it),
    so that every window serves matches that cross a seam."""
    lens = g.integers(min_len, max_len + 1, num).astype(np.int32)
    kind = g.random(num)
    sub = kind < substring_share
    starts = g.integers(0, len(text) - max_len, num)
    if seam_share:
        bound = seam_size * g.integers(1, len(text) // seam_size, num)
        back = 1 + (g.random(num) * (lens - 1)).astype(np.int64)
        starts = np.where(kind < seam_share, bound - back, starts)
    rand = g.integers(0, sigma, (num, max_len))
    cols = np.arange(max_len)
    pats = np.where(sub[:, None], text[starts[:, None] + cols[None, :]],
                    rand).astype(np.int32)
    pats[cols[None, :] >= lens[:, None]] = sigma
    return pats, lens


def zipf_draws(g: np.random.Generator, pool: int, num: int,
               exponent: float) -> np.ndarray:
    """(num,) indices into a pool of ``pool`` items, Zipf(exponent)
    popular over a shuffled order of the pool."""
    p = np.arange(1, pool + 1, dtype=np.float64) ** (-exponent)
    order = g.permutation(pool)
    return order[g.choice(pool, size=num, p=p / p.sum())]
