"""Sharded batched analytics engine over stacked wavelet-matrix shards.

Mirrors ``repro.index.sharded.ShardedTextIndex``: per-shard structures with
identical static geometry stack leaf-wise into one pytree with a leading
``(num_shards,)`` axis, so a query batch fans across all shards as a single
``vmap`` and the whole serving path is one jitted kernel.

Cross-shard reductions keep every op *exact* (not a merge of per-shard
approximations):

* ``count``     — per-shard orthogonal counts sum.
* ``quantile``  — count-then-refine: at each bit level the zero counts of
                  every shard's interval are summed before branching, so
                  all shards descend in lockstep on the global k.
* ``top-k``     — one greedy frontier whose nodes carry a per-shard
                  interval vector; a node's weight is the summed width.
* ``distinct``  — per-shard histograms sum, then count non-zeros (a symbol
                  present in several shards is counted once).

Module-level functions take the raw stacked ``WaveletMatrix`` + geometry so
``CompressedCorpus`` can delegate without a circular import; the
``ShardedAnalytics`` dataclass is the serving-layer handle.

Degraded mode: every op takes an optional per-shard ``available`` mask
(engine field, default all-available). An unavailable shard contributes an
*empty* local range — its ``hi`` clamps to ``lo`` before the reduction —
so every op serves exactly the surviving data with no special-casing in
the descent logic: counts/histograms/distinct cover only available
shards, quantiles rank within the covered positions. ``coverage`` reports
the covered fraction per query, and ``range_count_bounds`` /
``range_histogram_bounds`` bracket the true full-corpus answer (lower =
covered count, upper = lower + uncovered positions).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.wavelet_matrix import (WaveletMatrix, wm_child_interval,
                                       wm_interval_zeros)

from . import range_ops

_I32 = jnp.int32


def _num_shards(shards: WaveletMatrix) -> int:
    return jax.tree.leaves(shards)[0].shape[0]


def _shard(shards: WaveletMatrix, s) -> WaveletMatrix:
    return jax.tree.map(lambda l: l[s], shards)


def local_ranges(shard_bits: int, num_shards: int, n: int,
                 lo: jax.Array, hi: jax.Array):
    """Decompose global [lo, hi) into per-shard local ranges.

    Returns ``(los, his)`` of shape ``(S,) + lo.shape``: shard ``s`` covers
    global positions ``[s·2^shard_bits, (s+1)·2^shard_bits)``; its local
    range is the (possibly empty) intersection, clipped so the padded tail
    of the last shard (positions ≥ n) is never touched.
    """
    size = 1 << shard_bits
    lo = jnp.clip(jnp.asarray(lo, _I32), 0, n)
    hi = jnp.clip(jnp.asarray(hi, _I32), 0, n)
    hi = jnp.maximum(hi, lo)
    bases = (jnp.arange(num_shards, dtype=_I32) << shard_bits)
    bases = bases.reshape((num_shards,) + (1,) * jnp.ndim(lo))
    los = jnp.clip(lo[None] - bases, 0, size)
    his = jnp.clip(hi[None] - bases, 0, size)
    return los, his


def mask_ranges(los: jax.Array, his: jax.Array, available):
    """Clamp the local ranges of unavailable shards to empty.

    ``available``: (S,) bool mask or None (all available). Emptying the
    range is the single masking primitive every degraded-mode op shares —
    the descent/reduction logic downstream never sees the mask.
    """
    if available is None:
        return los, his
    S = los.shape[0]
    m = jnp.asarray(available, bool).reshape((S,) + (1,) * (los.ndim - 1))
    return los, jnp.where(m, his, los)


# --------------------------------------------------------------------------
# exact cross-shard ops on the stacked pytree
# --------------------------------------------------------------------------

def sharded_range_count(shards: WaveletMatrix, shard_bits: int, n: int,
                        lo, hi, sym_lo, sym_hi,
                        available=None) -> jax.Array:
    """Orthogonal range count over the whole corpus: per-shard counts sum.
    Broadcasts over batched query arrays. ``available`` masks shards out
    (degraded mode: the count covers surviving shards only)."""
    S = _num_shards(shards)
    los, his = mask_ranges(*local_ranges(shard_bits, S, n, lo, hi),
                           available)
    per = jax.vmap(
        lambda wm, a, b: range_ops.range_count(wm, a, b, sym_lo, sym_hi)
    )(shards, los, his)
    return jnp.sum(per, axis=0)


def sharded_coverage(shard_bits: int, num_shards: int, n: int, lo, hi,
                     available) -> jax.Array:
    """Fraction of [lo, hi) positions living on available shards.

    1.0 for fully-covered (or empty) queries; broadcasts over batches.
    The explicit honesty signal degraded-mode answers ship with.
    """
    return _coverage_parts(shard_bits, num_shards, n, lo, hi, available)[2]


def _coverage_parts(shard_bits: int, num_shards: int, n: int, lo, hi,
                    available):
    """(total, covered, coverage) positions of [lo, hi). Coverage is
    exactly 1.0 whenever every position is covered: the TPU's float
    division is not exact, so x / x need not be 1."""
    los, his = local_ranges(shard_bits, num_shards, n, lo, hi)
    total = jnp.sum(his - los, axis=0)
    _, mhis = mask_ranges(los, his, available)
    covered = jnp.sum(mhis - los, axis=0)
    frac = covered.astype(jnp.float32) / jnp.maximum(total, 1).astype(
        jnp.float32)
    return total, covered, jnp.where(covered == total, jnp.float32(1.0),
                                     frac)


def sharded_range_count_bounds(shards: WaveletMatrix, shard_bits: int,
                               n: int, lo, hi, sym_lo, sym_hi,
                               available=None):
    """(lower, upper, coverage) bracketing the true full-corpus count.

    ``lower`` counts surviving shards; every uncovered position could hold
    a matching symbol, so ``upper = lower + uncovered``. With full
    availability lower == upper == the exact count.
    """
    S = _num_shards(shards)
    lower = sharded_range_count(shards, shard_bits, n, lo, hi,
                                sym_lo, sym_hi, available)
    total, covered, cov = _coverage_parts(shard_bits, S, n, lo, hi,
                                          available)
    return lower, lower + (total - covered), cov


def sharded_range_quantile(shards: WaveletMatrix, shard_bits: int, n: int,
                           lo, hi, k, available=None) -> jax.Array:
    """Global k-th smallest symbol in [lo, hi): count-then-refine descent.

    Every shard keeps its own interval; the branch decision at each level
    compares k against the *summed* zero count, then all shards take the
    same child. O(S·logσ) rank probes per query. Broadcasts over batches.
    Under an ``available`` mask the descent ranks within the covered
    positions only (k clips to the covered total).
    """
    S = _num_shards(shards)
    nbits = shards.nbits
    los, his = mask_ranges(*local_ranges(shard_bits, S, n, lo, hi),
                           available)
    total = jnp.sum(his - los, axis=0)
    k = jnp.clip(jnp.asarray(k, _I32), 0, jnp.maximum(total - 1, 0))
    empty = total <= 0
    sym = jnp.zeros_like(k)
    for l in range(nbits):
        lo0, hi0 = jax.vmap(
            lambda wm, a, b: wm_interval_zeros(wm, l, a, b)
        )(shards, los, his)
        z = jnp.sum(hi0 - lo0, axis=0)
        bit = (k >= z).astype(_I32)
        k = jnp.where(bit == 1, k - z, k)
        sym = (sym << 1) | bit
        los, his = jax.vmap(
            lambda wm, a, b, z0, h0: wm_child_interval(wm, l, a, b, bit,
                                                       z0, h0)
        )(shards, los, his, lo0, hi0)
    return jnp.where(empty, jnp.asarray(-1, _I32), sym)


def sharded_range_quantile_fused(shards: WaveletMatrix, shard_bits: int,
                                 n: int, lo, hi, k,
                                 interpret: bool | None = None,
                                 available=None) -> jax.Array:
    """Kernel form of ``sharded_range_quantile``: the whole count-then-
    refine descent (all shards × all levels) runs as ONE fused Pallas
    launch per query block (``kernels.wm_quantile_sharded_batch``), with
    every shard's bitmaps + rank directories resident in VMEM. Exact same
    results; (Q,) batches only (the XLA path broadcasts arbitrary shapes).
    Degraded mode (an ``available`` mask) routes to the XLA descent — the
    fused kernel assumes full shard residency.
    """
    if available is not None:
        obs.counter("analytics.path", op="quantile",
                    path="degraded_xla").inc()
        return sharded_range_quantile(shards, shard_bits, n, lo, hi, k,
                                      available)
    obs.counter("analytics.path", op="quantile", path="kernel").inc()
    from repro.kernels import ops as _kops
    return _kops.wm_quantile_sharded_batch(shards, shard_bits, n, lo, hi, k,
                                           interpret=interpret)


def sharded_range_quantile_bracket(shards: WaveletMatrix, shard_bits: int,
                                   n: int, lo, hi, k, levels: int,
                                   available=None):
    """Reduced-refinement quantile: descend only the top ``levels`` of the
    ``nbits`` bit levels and return ``(sym_lo, sym_hi)`` — the half-open
    symbol bracket ``[sym_lo, sym_hi)`` that provably contains the exact
    k-th smallest. ``levels == nbits`` collapses the bracket to
    ``[sym, sym+1)`` (the exact answer); each level shaved halves the
    descent cost (O(S·levels) rank probes) and doubles the bracket width
    (``2^(nbits-levels)`` symbols). The degradation ladder's cheap
    quantile rung: honest because the bracket is reported, not a point
    estimate. Empty/uncovered ranges return ``(-1, -1)``.
    """
    S = _num_shards(shards)
    nbits = shards.nbits
    levels = max(0, min(int(levels), nbits))
    los, his = mask_ranges(*local_ranges(shard_bits, S, n, lo, hi),
                           available)
    total = jnp.sum(his - los, axis=0)
    k = jnp.clip(jnp.asarray(k, _I32), 0, jnp.maximum(total - 1, 0))
    empty = total <= 0
    sym = jnp.zeros_like(k)
    for l in range(levels):
        lo0, hi0 = jax.vmap(
            lambda wm, a, b: wm_interval_zeros(wm, l, a, b)
        )(shards, los, his)
        z = jnp.sum(hi0 - lo0, axis=0)
        bit = (k >= z).astype(_I32)
        k = jnp.where(bit == 1, k - z, k)
        sym = (sym << 1) | bit
        los, his = jax.vmap(
            lambda wm, a, b, z0, h0: wm_child_interval(wm, l, a, b, bit,
                                                       z0, h0)
        )(shards, los, his, lo0, hi0)
    width = nbits - levels
    sym_lo = sym << width
    sym_hi = (sym + 1) << width
    neg1 = jnp.asarray(-1, _I32)
    return (jnp.where(empty, neg1, sym_lo),
            jnp.where(empty, neg1, sym_hi))


def sharded_range_topk(shards: WaveletMatrix, shard_bits: int, n: int,
                       lo, hi, k: int, available=None):
    """Exact global top-k: per-shard histograms sum, then one ``top_k``.

    ``lo``/``hi`` may be scalars or (B,) batches; returns (..., k) syms and
    counts sorted by descending global count, (-1, 0) padded.
    """
    hist = sharded_range_histogram(shards, shard_bits, n, lo, hi, available)
    return range_ops.topk_from_histogram(hist, k)


def sharded_range_topk_greedy(shards: WaveletMatrix, shard_bits: int,
                              n: int, lo, hi, k: int,
                              budget: int | None = None,
                              prune: bool = True, available=None):
    """Greedy global top-k: ONE frontier whose nodes carry a per-shard
    interval vector (weight = summed width) — a true global walk, not a
    merge of per-shard top-k lists. Same budget/exactness/``prune``
    trade-offs as ``range_ops.range_topk_greedy``; O(budget·S·logσ)
    probes per query.
    """
    S = _num_shards(shards)
    wms = [_shard(shards, s) for s in range(S)]

    def one(lo_q, hi_q):
        los, his = mask_ranges(*local_ranges(shard_bits, S, n, lo_q, hi_q),
                               available)
        return range_ops._topk_frontier(
            wms, [los[s] for s in range(S)], [his[s] for s in range(S)],
            k, budget, prune)[:2]

    lo = jnp.asarray(lo, _I32)
    if lo.ndim == 0:
        return one(lo, hi)
    return jax.vmap(one)(lo, jnp.asarray(hi, _I32))


def sharded_range_histogram(shards: WaveletMatrix, shard_bits: int, n: int,
                            lo, hi, available=None) -> jax.Array:
    """Global per-symbol counts for [lo, hi): per-shard histograms sum.
    Scalar or (B,) queries → (..., 2^nbits) int32."""
    S = _num_shards(shards)

    def one(lo_q, hi_q):
        los, his = mask_ranges(*local_ranges(shard_bits, S, n, lo_q, hi_q),
                               available)
        per = jax.vmap(
            lambda wm, a, b: range_ops.range_histogram(wm, a, b)
        )(shards, los, his)
        return jnp.sum(per, axis=0)

    lo = jnp.asarray(lo, _I32)
    if lo.ndim == 0:
        return one(lo, hi)
    return jax.vmap(one)(lo, jnp.asarray(hi, _I32))


def sharded_range_histogram_bounds(shards: WaveletMatrix, shard_bits: int,
                                   n: int, lo, hi, available=None):
    """(hist_lower, uncovered, coverage): per-symbol lower bounds plus the
    per-query count of uncovered positions — any symbol's true count is in
    [hist_lower[c], hist_lower[c] + uncovered]."""
    S = _num_shards(shards)
    hist = sharded_range_histogram(shards, shard_bits, n, lo, hi, available)
    total, covered, cov = _coverage_parts(shard_bits, S, n, lo, hi,
                                          available)
    return hist, total - covered, cov


def sharded_range_distinct(shards: WaveletMatrix, shard_bits: int, n: int,
                           lo, hi, available=None) -> jax.Array:
    """# of distinct symbols in global [lo, hi) (union across shards)."""
    hist = sharded_range_histogram(shards, shard_bits, n, lo, hi, available)
    return jnp.sum(hist > 0, axis=-1).astype(_I32)


@jax.jit
def _probe_count(shards: WaveletMatrix, s: jax.Array,
                 sigma: jax.Array) -> jax.Array:
    """One-position count on shard ``s`` — the liveness probe, compiled
    once for every shard index (one dispatch per probe)."""
    zero = jnp.asarray(0, _I32)
    return range_ops.range_count(_shard(shards, s), zero,
                                 jnp.asarray(1, _I32), zero, sigma)


# --------------------------------------------------------------------------
# serving-layer handle
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class ShardedAnalytics:
    """Stacked per-shard wavelet matrices + corpus geometry.

    The analytics twin of ``ShardedTextIndex``: build once (or adopt a
    ``CompressedCorpus``'s shards — same layout, zero copy), then serve
    batched range queries as single jitted vmapped calls.
    """
    shards: WaveletMatrix          # every leaf has a leading (S,) axis
    n: int = field(metadata=dict(static=True))
    sigma: int = field(metadata=dict(static=True))
    shard_bits: int = field(metadata=dict(static=True))
    #: (S,) bool per-shard availability, or None for full availability.
    #: Unavailable shards are served around, not crashed into — see the
    #: module docstring's degraded-mode contract.
    available: jax.Array | None = None

    @property
    def num_shards(self) -> int:
        return _num_shards(self.shards)

    @property
    def shard_size(self) -> int:
        return 1 << self.shard_bits

    @property
    def degraded(self) -> bool:
        return self.available is not None

    # ---- availability management --------------------------------------
    def with_availability(self, available) -> "ShardedAnalytics":
        """Engine serving only the shards where ``available`` is True
        (pass ``None`` to restore full availability)."""
        if available is not None:
            available = jnp.asarray(available, bool)
            if available.shape != (self.num_shards,):
                raise ValueError(
                    f"availability mask shape {available.shape} != "
                    f"({self.num_shards},)")
        return dataclasses.replace(self, available=available)

    def drop_shards(self, shard_ids) -> "ShardedAnalytics":
        """Mark the given shard indices unavailable (on top of the current
        mask) — the degraded-serving entry point for lost shards."""
        mask = (jnp.ones((self.num_shards,), bool)
                if self.available is None else self.available)
        mask = mask.at[jnp.asarray(shard_ids, _I32)].set(False)
        return dataclasses.replace(self, available=mask)

    def coverage(self, lo, hi) -> jax.Array:
        """Fraction of [lo, hi) positions on available shards (1.0 when
        the engine is fully available)."""
        return sharded_coverage(self.shard_bits, self.num_shards, self.n,
                                lo, hi, self.available)

    def shard(self, s) -> WaveletMatrix:
        return _shard(self.shards, s)

    def bits_per_token(self) -> float:
        total = sum(l.size * l.dtype.itemsize * 8
                    for l in jax.tree.leaves(self.shards))
        return total / max(1, self.n)

    @classmethod
    def from_corpus(cls, corpus) -> "ShardedAnalytics":
        """Adopt a ``CompressedCorpus``'s shards (no rebuild, no copy)."""
        return cls(shards=corpus.shards, n=corpus.n, sigma=corpus.sigma,
                   shard_bits=corpus.shard_bits)

    # ---- incremental ingest / hot swap ---------------------------------
    def add_shards(self, new_shards: WaveletMatrix, added_tokens: int,
                   new_available=None) -> "ShardedAnalytics":
        """Next-generation engine with ``new_shards`` appended.

        ``new_shards`` is a stacked ``(K,)``-leaf pytree with this
        engine's static geometry (same shard size, levels, sample rate);
        ``added_tokens`` is the true token count the new shards carry
        (``(K-1)·shard_size < added_tokens ≤ K·shard_size`` — only the
        final shard may be partial). ``new_available`` masks freshly
        quarantined shards (honest partial coverage during ingest); the
        combined mask collapses back to ``None`` when everything is
        available. The result is a *new value* — publish it through
        ``ingest.serving.GenerationServer.swap_generation`` so in-flight
        query batches finish against the old generation. ``n`` is a
        static field, so each generation compiles its query kernels once.
        """
        if self.n != self.num_shards << self.shard_bits:
            raise ValueError(
                f"cannot append to a corpus with a partial tail shard "
                f"(n={self.n}, {self.num_shards} shards of "
                f"{self.shard_size})")
        K = jax.tree.leaves(new_shards)[0].shape[0]
        added_tokens = int(added_tokens)
        if not ((K - 1) << self.shard_bits) < added_tokens \
                <= (K << self.shard_bits):
            raise ValueError(
                f"added_tokens={added_tokens} does not fill {K} shard(s) "
                f"of {self.shard_size}")
        merged = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                              self.shards, new_shards)
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
        obs.counter("ingest.shard_swap", layer="analytics").inc()
        return dataclasses.replace(self, shards=merged,
                                   n=self.n + added_tokens,
                                   available=mask)

    # ---- batched queries (each one jittable, vmapped internally) -------
    def range_quantile(self, lo, hi, k, use_kernel: bool = False
                       ) -> jax.Array:
        """Global k-th smallest in [lo, hi). ``use_kernel`` routes (Q,)
        batches through the fused sharded Pallas descent (one launch per
        query block, identical results); a degraded engine always takes
        the XLA path."""
        obs.counter("analytics.op", op="quantile").inc()
        if use_kernel:
            return sharded_range_quantile_fused(self.shards, self.shard_bits,
                                                self.n, lo, hi, k,
                                                available=self.available)
        obs.counter("analytics.path", op="quantile", path="xla").inc()
        return sharded_range_quantile(self.shards, self.shard_bits, self.n,
                                      lo, hi, k, self.available)

    def range_quantile_bracket(self, lo, hi, k, levels: int):
        """(sym_lo, sym_hi) bracketing the exact k-th smallest after a
        descent truncated to ``levels`` bit levels — the degradation
        ladder's reduced-refinement quantile (see
        ``sharded_range_quantile_bracket``)."""
        obs.counter("analytics.op", op="quantile_bracket").inc()
        return sharded_range_quantile_bracket(self.shards, self.shard_bits,
                                              self.n, lo, hi, k, levels,
                                              self.available)

    def probe_shard(self, s: int, clock=None) -> bool:
        """Liveness probe of one shard: a minimal single-shard count that
        honours any chaos-armed ``robust.faults.shard_latency`` stall
        (slept on the injectable ``clock`` — real stall under the system
        clock, instant logical stall under ``FakeClock``). The serving
        front-end's circuit breakers hedge these probes under a timeout —
        a stuck shard turns into an open breaker (degraded coverage)
        instead of a stalled queue. Returns True on success.
        """
        from repro.robust.clock import SYSTEM_CLOCK
        from repro.robust.faults import shard_latency
        clock = clock if clock is not None else SYSTEM_CLOCK
        delay = shard_latency(s)
        if delay > 0:
            clock.sleep(delay)
        out = _probe_count(self.shards, jnp.asarray(s, _I32),
                           jnp.asarray(self.sigma, _I32))
        return bool(jax.block_until_ready(out) >= 0)

    def range_count(self, lo, hi, sym_lo, sym_hi) -> jax.Array:
        obs.counter("analytics.op", op="count").inc()
        return sharded_range_count(self.shards, self.shard_bits, self.n,
                                   lo, hi, sym_lo, sym_hi, self.available)

    def range_count_bounds(self, lo, hi, sym_lo, sym_hi):
        """(lower, upper, coverage) bracketing the full-corpus count —
        the honest degraded-mode answer."""
        obs.counter("analytics.op", op="count_bounds").inc()
        return sharded_range_count_bounds(self.shards, self.shard_bits,
                                          self.n, lo, hi, sym_lo, sym_hi,
                                          self.available)

    def range_topk(self, lo, hi, k: int):
        obs.counter("analytics.op", op="topk").inc()
        return sharded_range_topk(self.shards, self.shard_bits, self.n,
                                  lo, hi, k, self.available)

    def range_topk_greedy(self, lo, hi, k: int, budget: int | None = None,
                          prune: bool = True):
        obs.counter("analytics.op", op="topk_greedy").inc()
        return sharded_range_topk_greedy(self.shards, self.shard_bits,
                                         self.n, lo, hi, k, budget, prune,
                                         self.available)

    def range_distinct(self, lo, hi) -> jax.Array:
        obs.counter("analytics.op", op="distinct").inc()
        return sharded_range_distinct(self.shards, self.shard_bits, self.n,
                                      lo, hi, self.available)

    def range_histogram(self, lo, hi) -> jax.Array:
        obs.counter("analytics.op", op="histogram").inc()
        return sharded_range_histogram(self.shards, self.shard_bits, self.n,
                                       lo, hi, self.available)

    def range_histogram_bounds(self, lo, hi):
        """(hist_lower, uncovered, coverage): true per-symbol counts lie
        in [hist_lower[c], hist_lower[c] + uncovered]."""
        obs.counter("analytics.op", op="histogram_bounds").inc()
        return sharded_range_histogram_bounds(self.shards, self.shard_bits,
                                              self.n, lo, hi, self.available)


def build_sharded_analytics(tokens, sigma: int, *, shard_bits: int = 16,
                            tau: int = 8, big_step: str = "compose",
                            sample_rate: int = 512,
                            parallel: str | bool = "auto"
                            ) -> ShardedAnalytics:
    """Build the engine from a raw token stream (via the compressed-store
    shard builder, which pmaps/vmaps shard construction when it can)."""
    from repro.data.compressed_store import build_compressed_corpus
    corpus = build_compressed_corpus(tokens, sigma, shard_bits=shard_bits,
                                     tau=tau, big_step=big_step,
                                     sample_rate=sample_rate,
                                     parallel=parallel)
    return ShardedAnalytics.from_corpus(corpus)
