"""Shard-parallel full-text index: FM-index per shard, stacked leaf-wise.

Mirrors ``CompressedCorpus``'s layout exactly: every shard's ``FMIndex``
pytree has identical static geometry (power-of-two shard size, shared
alphabet), so the shards stack leaf-wise into ONE pytree with a leading
``(num_shards,)`` axis, and a batch of patterns against all shards is a
single ``vmap``-over-shards of the vmapped-over-patterns backward search —
one jitted kernel for the whole corpus.

The last shard is padded with the out-of-alphabet symbol σ (indexed with an
alphabet of σ+1), which cannot appear in a query, so padding never produces
phantom matches.

Cross-shard stitching: per-shard FM-indexes alone cannot see a match that
*spans a shard boundary*. ``count`` therefore adds a seam pass: every
internal boundary stores a ±``seam_overlap``-token window of the raw
stream, and a vectorized sliding compare counts the matches that genuinely
cross the boundary (within-shard matches are excluded by the crossing
condition, so nothing is double-counted). Counts are exact for pattern
lengths ≤ min(seam_overlap + 1, shard_size). ``locate`` still reports
within-shard positions only — seam hits are count-only for now (ROADMAP).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.data.shard_build import build_shards_stacked

from .fm_index import FMIndex, build_fm_index, fm_count, fm_locate

_I32 = jnp.int32

#: filler for seam-window slots outside the corpus. Distinct from the -1
#: that pattern sanitization emits, so masked query symbols can never
#: "match" masked window slots.
_SEAM_PAD = -2


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class ShardedTextIndex:
    """Stacked per-shard FM-indexes + seam windows + corpus geometry."""
    shards: FMIndex                # every leaf has a leading (S,) axis
    seam_windows: jax.Array        # (S-1, 2·seam_overlap) int32, _SEAM_PAD
    #                                filled outside [0, n)
    n: int = field(metadata=dict(static=True))       # true corpus length
    sigma: int = field(metadata=dict(static=True))   # raw vocab size
    shard_bits: int = field(metadata=dict(static=True))
    seam_overlap: int = field(metadata=dict(static=True))
    #: (S,) bool per-shard availability, or None for full availability.
    #: Degraded mode: unavailable shards contribute 0 within-shard matches,
    #: seams touching them are skipped, and their locate hits are masked —
    #: ``coverage()`` / ``count_bounds`` report how much corpus is served.
    available: jax.Array | None = None

    @property
    def shard_size(self) -> int:
        return 1 << self.shard_bits

    @property
    def num_shards(self) -> int:
        return jax.tree.leaves(self.shards)[0].shape[0]

    @property
    def degraded(self) -> bool:
        return self.available is not None

    # ---- availability management -------------------------------------
    def with_availability(self, available) -> "ShardedTextIndex":
        """Index serving only the shards where ``available`` is True
        (``None`` restores full availability)."""
        if available is not None:
            available = jnp.asarray(available, bool)
            if available.shape != (self.num_shards,):
                raise ValueError(
                    f"availability mask shape {available.shape} != "
                    f"({self.num_shards},)")
        return dataclasses.replace(self, available=available)

    def drop_shards(self, shard_ids) -> "ShardedTextIndex":
        """Mark the given shard indices unavailable (cumulative)."""
        mask = (jnp.ones((self.num_shards,), bool)
                if self.available is None else self.available)
        mask = mask.at[jnp.asarray(shard_ids, _I32)].set(False)
        return dataclasses.replace(self, available=mask)

    def _shard_sizes(self) -> jax.Array:
        """(S,) true (unpadded) token count of each shard."""
        starts = jnp.arange(self.num_shards, dtype=_I32) << self.shard_bits
        return jnp.clip(jnp.asarray(self.n, _I32) - starts, 0,
                        self.shard_size)

    def coverage(self) -> jax.Array:
        """Fraction of corpus positions on available shards (float32)."""
        if self.available is None:
            return jnp.float32(1.0)
        covered = jnp.sum(jnp.where(self.available, self._shard_sizes(), 0))
        # exact 1.0 when all is covered: TPU float division is not exact
        return jnp.where(covered == self.n, jnp.float32(1.0),
                         covered.astype(jnp.float32)
                         / jnp.float32(max(1, self.n)))

    def shard(self, s: jax.Array) -> FMIndex:
        return jax.tree.map(lambda l: l[s], self.shards)

    def probe_shard(self, s: int, clock=None) -> bool:
        """Liveness probe of one shard: a minimal single-shard backward
        search that honours any chaos-armed ``robust.faults.shard_latency``
        stall (slept on the injectable ``clock``). The serving front-end's
        circuit breakers hedge these probes under a timeout so a stuck
        shard degrades coverage instead of stalling the queue. Returns
        True on success.
        """
        from repro.robust.clock import SYSTEM_CLOCK
        from repro.robust.faults import shard_latency
        clock = clock if clock is not None else SYSTEM_CLOCK
        delay = shard_latency(s)
        if delay > 0:
            clock.sleep(delay)
        fm = self.shard(int(s))
        pat = jnp.zeros((1, 1), _I32)
        out = fm_count(fm, pat, jnp.ones((1,), _I32))
        return bool(jax.block_until_ready(out)[0] >= 0)

    # ---- incremental ingest / hot swap -------------------------------
    def add_shards(self, new_shards: FMIndex, new_seams: jax.Array,
                   added_tokens: int, new_available=None
                   ) -> "ShardedTextIndex":
        """Next-generation index with ``new_shards`` appended.

        ``new_shards``: stacked ``(K,)``-leaf FM-index pytree with this
        index's static geometry. ``new_seams``: the ``(K, 2·seam_overlap)``
        boundary windows *preceding* each new shard (the seam between the
        old tail and the first new shard, then between consecutive new
        shards — ``ingest.ShardIngester.seam_windows`` derives them from
        the journaled head/tail sidecars). ``added_tokens`` is the true
        token count added (only the final shard may be partial — the old
        corpus must end on a shard boundary). ``new_available`` masks
        quarantined shards. The result is a new value; publish it through
        ``GenerationServer.swap_generation`` for epoch-fenced hot swap.
        """
        if self.n != self.num_shards << self.shard_bits:
            raise ValueError(
                f"cannot append to an index with a partial tail shard "
                f"(n={self.n}, {self.num_shards} shards of "
                f"{self.shard_size})")
        K = jax.tree.leaves(new_shards)[0].shape[0]
        added_tokens = int(added_tokens)
        if not ((K - 1) << self.shard_bits) < added_tokens \
                <= (K << self.shard_bits):
            raise ValueError(
                f"added_tokens={added_tokens} does not fill {K} shard(s) "
                f"of {self.shard_size}")
        new_seams = jnp.asarray(new_seams, _I32)
        if new_seams.shape != (K, 2 * self.seam_overlap):
            raise ValueError(
                f"new_seams shape {new_seams.shape} != "
                f"({K}, {2 * self.seam_overlap})")
        merged = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                              self.shards, new_shards)
        seams = jnp.concatenate([self.seam_windows, new_seams], axis=0)
        if self.available is None and new_available is None:
            mask = None
        else:
            old = (jnp.ones((self.num_shards,), bool)
                   if self.available is None else self.available)
            new = (jnp.ones((K,), bool) if new_available is None
                   else jnp.asarray(new_available, bool).reshape((K,)))
            mask = jnp.concatenate([old, new])
            if bool(jnp.all(mask)):
                mask = None
        obs.counter("ingest.shard_swap", layer="index").inc()
        return dataclasses.replace(self, shards=merged, seam_windows=seams,
                                   n=self.n + added_tokens, available=mask)

    def bits_per_token(self) -> float:
        total = sum(l.size * l.dtype.itemsize * 8
                    for l in jax.tree.leaves(self.shards))
        return total / max(1, self.n)

    # ------------------------------------------------------------------
    def _sanitize(self, patterns: jax.Array, lengths: jax.Array):
        """Coerce shapes and mask symbols outside the *corpus* vocabulary.

        Shards are indexed with the widened alphabet σ+1 (pad symbol σ is
        in-alphabet for the per-shard FM-index), so out-of-vocab query
        symbols — σ included — are rewritten to -1 here, which the
        backward search treats as match-nothing. Without this, a query
        containing σ would count the tail shard's padding. Zero-length
        patterns become a 1-symbol match-nothing pattern: the empty query
        counts 0 at this layer (an unrestricted SA range over every shard
        — padding included — is never what a corpus caller wants).
        """
        patterns = jnp.atleast_2d(jnp.asarray(patterns, _I32))
        lengths = jnp.atleast_1d(jnp.asarray(lengths, _I32))
        in_vocab = (patterns >= 0) & (patterns < self.sigma)
        patterns = jnp.where(in_vocab, patterns, jnp.asarray(-1, _I32))
        empty = lengths <= 0
        patterns = patterns.at[:, 0].set(
            jnp.where(empty, jnp.asarray(-1, _I32), patterns[:, 0]))
        return patterns, jnp.where(empty, 1, lengths)

    def count(self, patterns: jax.Array, lengths: jax.Array) -> jax.Array:
        """Total matches per pattern, (B,) int32 — within-shard matches
        from the FM-indexes plus boundary-crossing matches from the seam
        windows. Exact for lengths ≤ min(seam_overlap + 1, shard_size).
        On a degraded index this counts surviving shards only (a lower
        bound on the true count — ``count_bounds`` brackets it)."""
        obs.counter("index.op", op="count",
                    path="degraded" if self.degraded else "full").inc()
        patterns = jnp.atleast_2d(jnp.asarray(patterns, _I32))
        within = jnp.sum(self.count_by_shard(patterns, lengths), axis=0)
        return within + self._seam_count(*self._sanitize(patterns, lengths))

    def count_bounds(self, patterns: jax.Array, lengths: jax.Array):
        """(lower, upper, coverage) bracketing the full-corpus count.

        ``lower`` is the degraded ``count``. Every missed match either
        starts on an unavailable shard (≤ its position count) or crosses
        a skipped seam (≤ length−1 starts per seam), so
        ``upper = lower + unavailable_positions + skipped_seams·(len−1)``.
        Fully-available indexes return lower == upper, coverage 1.0.
        """
        obs.counter("index.op", op="count_bounds",
                    path="degraded" if self.degraded else "full").inc()
        lower = self.count(patterns, lengths)
        if self.available is None:
            return lower, lower, jnp.float32(1.0)
        uncovered = jnp.sum(
            jnp.where(self.available, 0, self._shard_sizes()))
        seam_ok = self.available[:-1] & self.available[1:]
        skipped = jnp.sum(~seam_ok).astype(_I32)
        lengths = jnp.atleast_1d(jnp.asarray(lengths, _I32))
        extra = uncovered + skipped * jnp.maximum(lengths - 1, 0)
        return lower, lower + extra, self.coverage()

    def _seam_count(self, patterns: jax.Array,
                    lengths: jax.Array) -> jax.Array:
        """(B,) matches that cross a shard boundary (sanitized inputs).

        A length-l match at window offset o of a seam (boundary at window
        center ov) crosses iff o < ov < o + l; the sliding compare is one
        broadcast equality over (B patterns × seams × offsets × positions).
        Patterns longer than the exactness domain min(ov+1, shard_size)
        contribute 0 here (their count stays within-shard-only) rather
        than a partial crossing count: beyond ov+1 the window cannot hold
        every crossing start, and beyond shard_size a match could cross
        two seams and double-count.
        """
        ns, width = self.seam_windows.shape
        ov = self.seam_overlap
        B, L = patterns.shape
        if ns == 0 or ov == 0:
            return jnp.zeros((B,), _I32)
        lmax = min(ov + 1, self.shard_size)
        o = jnp.arange(width, dtype=_I32)                       # offsets
        t = jnp.arange(L, dtype=_I32)                           # positions
        idx = jnp.minimum(o[:, None] + t[None, :], width - 1)   # (O, L)
        win = self.seam_windows[:, idx]                         # (ns, O, L)
        pat = patterns[:, None, None, :]                        # (B,1,1,L)
        past_len = (t[None, :] >= lengths[:, None])[:, None, None, :]
        hit = jnp.all((win[None] == pat) | past_len, axis=-1)   # (B, ns, O)
        ol = o[None, :] + lengths[:, None]                      # (B, O)
        span = ((o[None, :] < ov) & (ol > ov) & (ol <= width)
                & (lengths[:, None] <= lmax))[:, None, :]
        crossing = hit & span
        if self.available is not None:
            # seam s spans shards s and s+1 — both must be available
            seam_ok = self.available[:-1] & self.available[1:]
            crossing = crossing & seam_ok[None, :, None]
        return jnp.sum(crossing, axis=(1, 2)).astype(_I32)

    def count_by_shard(self, patterns: jax.Array,
                       lengths: jax.Array) -> jax.Array:
        """(S, B) per-shard match counts (distribution analytics).

        One vmap over the stacked shard axis of the per-shard batched
        backward search. Unavailable shards report 0.
        """
        patterns, lengths = self._sanitize(patterns, lengths)
        per = jax.vmap(lambda fm: fm_count(fm, patterns, lengths))(
            self.shards)
        if self.available is not None:
            per = jnp.where(self.available[:, None], per, 0)
        return per

    def locate(self, patterns: jax.Array, lengths: jax.Array,
               max_hits_per_shard: int = 8) -> jax.Array:
        """Global match positions, (B, S·max_hits_per_shard) int32.

        Per-shard local hits are rebased by ``s · shard_size``; slots past
        each shard's true hit count are -1. Sorted ascending per pattern
        with the -1 padding swept to the back.
        """
        obs.counter("index.op", op="locate",
                    path="degraded" if self.degraded else "full").inc()
        patterns, lengths = self._sanitize(patterns, lengths)
        S = self.num_shards

        def per_shard(fm, base, ok):
            def one(p, l):
                local = fm_locate(fm, p, l, max_hits_per_shard)
                return jnp.where(ok & (local >= 0), local + base,
                                 jnp.asarray(-1, _I32))
            return jax.vmap(one)(patterns, lengths)        # (B, H)

        bases = jnp.arange(S, dtype=_I32) << self.shard_bits
        ok = (jnp.ones((S,), bool) if self.available is None
              else jnp.asarray(self.available, bool))
        hits = jax.vmap(per_shard)(self.shards, bases, ok)  # (S, B, H)
        flat = jnp.transpose(hits, (1, 0, 2)).reshape(patterns.shape[0], -1)
        big = jnp.where(flat < 0, jnp.asarray(jnp.iinfo(jnp.int32).max,
                                              _I32), flat)
        out = jnp.sort(big, axis=-1)
        return jnp.where(out == jnp.iinfo(jnp.int32).max,
                         jnp.asarray(-1, _I32), out)


def seam_windows_from_tokens(tokens: np.ndarray, num_shards: int,
                             shard_size: int, seam_overlap: int) -> np.ndarray:
    """(num_shards-1, 2·seam_overlap) raw-stream windows around each
    internal shard boundary, ``_SEAM_PAD``-filled outside [0, n)."""
    n = len(tokens)
    ns = max(0, num_shards - 1)
    width = 2 * seam_overlap
    win = np.full((ns, width), _SEAM_PAD, np.int32)
    for s in range(ns):
        p = (s + 1) * shard_size
        g0 = p - seam_overlap
        for o in range(width):
            g = g0 + o
            if 0 <= g < n:
                win[s, o] = tokens[g]
    return win


def build_sharded_index(tokens: np.ndarray, sigma: int, *,
                        shard_bits: int = 14, sample_rate: int = 32,
                        tau: int = 8, big_step: str = "compose",
                        bv_sample_rate: int = 512,
                        backend: str = "counting",
                        seam_overlap: int = 15,
                        parallel: str | bool = "auto") -> ShardedTextIndex:
    """Shard the token stream and run the full per-shard build pipeline
    (suffix array → BWT → wavelet matrix → SA samples) on every shard,
    stacking the resulting pytrees leaf-wise.

    Shard builds fan out over the device mesh via ``data.shard_build``
    (pmap across devices, vmap on one device when ``parallel=True``, else
    the sequential loop with its per-shard early exits). The tail shard is
    padded with the out-of-alphabet symbol σ. ``seam_overlap`` sets the
    half-width of the boundary windows that make ``count`` exact across
    shard seams for pattern lengths ≤ seam_overlap + 1 (0 disables).
    """
    n = int(len(tokens))
    shard_size = 1 << shard_bits
    num_shards = max(1, (n + shard_size - 1) // shard_size)
    pad = num_shards * shard_size - n
    toks = np.asarray(tokens, np.int64)
    if toks.size and (toks.min() < 0 or toks.max() >= sigma):
        raise ValueError(f"tokens outside [0, {sigma})")
    if pad:
        toks = np.concatenate([toks, np.full(pad, sigma, np.int64)])
    shards_np = toks.reshape(num_shards, shard_size)

    stacked = build_shards_stacked(
        lambda s: build_fm_index(s.astype(_I32), sigma + 1,
                                 sample_rate=sample_rate, tau=tau,
                                 big_step=big_step,
                                 bv_sample_rate=bv_sample_rate,
                                 backend=backend),
        shards_np, parallel=parallel)
    seams = seam_windows_from_tokens(np.asarray(tokens, np.int64),
                                     num_shards, shard_size, seam_overlap)
    return ShardedTextIndex(shards=stacked, seam_windows=jnp.asarray(seams),
                            n=n, sigma=sigma, shard_bits=shard_bits,
                            seam_overlap=seam_overlap)
