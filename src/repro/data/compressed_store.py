"""Wavelet-matrix compressed token store — the paper's technique as the
framework's corpus substrate.

Tokenized corpora are stored as a stack of fixed-size wavelet-matrix shards
over the token alphabet: ``⌈logσ⌉`` bits/token (e.g. 18 for qwen2's
σ=151936 — 1.8× smaller than uint32) plus the o(n) rank/select directories.
Construction per shard runs the paper's τ-chunked parallel algorithm
(Theorem 4.5); queries give O(logσ) random ``access`` (batch decoding),
``rank`` (corpus-frequency analytics, dedup heuristics) and ``select``
(locate the k-th occurrence — span queries for retrieval-style sampling).

Shards are stacked leaf-wise into one pytree so a batch of positions across
shards is a single vmapped query (shard id → leaf gather). Shard size is a
power of two so position → (shard, offset) is shift/mask.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analytics.engine import (sharded_range_count,
                                    sharded_range_distinct,
                                    sharded_range_histogram,
                                    sharded_range_quantile,
                                    sharded_range_topk)
from repro.core.wavelet_matrix import (WaveletMatrix, build_wavelet_matrix,
                                       num_levels, wm_access, wm_rank,
                                       wm_select)

from .shard_build import build_mode, build_shards_stacked

_I32 = jnp.int32


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class CompressedCorpus:
    """Sharded wavelet-matrix corpus + per-shard symbol histograms."""
    shards: WaveletMatrix          # leaves carry a leading (num_shards,) axis
    shard_counts: jax.Array        # (num_shards + 1, sigma) exclusive cumsum
    n: int = field(metadata=dict(static=True))
    sigma: int = field(metadata=dict(static=True))
    shard_bits: int = field(metadata=dict(static=True))

    # ---- geometry ----
    @property
    def shard_size(self) -> int:
        return 1 << self.shard_bits

    @property
    def num_shards(self) -> int:
        return self.shard_counts.shape[0] - 1

    @property
    def nbits(self) -> int:
        return num_levels(self.sigma)

    def shard(self, s: jax.Array) -> WaveletMatrix:
        return jax.tree.map(lambda l: l[s], self.shards)

    # ---- size accounting ----
    def bits_per_token(self) -> float:
        total_bits = sum(l.size * l.dtype.itemsize * 8
                         for l in jax.tree.leaves(self.shards))
        return total_bits / self.n

    def raw_bits_per_token(self) -> int:
        return 32

    # ---- queries ----
    def access(self, pos: jax.Array) -> jax.Array:
        """Decode tokens at arbitrary positions. pos: (...,) int."""
        pos = jnp.asarray(pos, _I32)
        sid = pos >> self.shard_bits
        off = pos & (self.shard_size - 1)

        def one(s, o):
            return wm_access(self.shard(s), o)

        flat = jax.vmap(one)(sid.reshape(-1), off.reshape(-1))
        return flat.reshape(pos.shape)

    def decode_slice(self, start: jax.Array, length: int) -> jax.Array:
        """Decode a contiguous span (batch serving path). Static length."""
        return self.access(jnp.asarray(start, _I32) + jnp.arange(length, dtype=_I32))

    def count(self, token: jax.Array, upto: Optional[jax.Array] = None) -> jax.Array:
        """# occurrences of ``token`` in [0, upto) (whole corpus if None)."""
        token = jnp.asarray(token, _I32)
        if upto is None:
            return self.shard_counts[-1, token]
        upto = jnp.asarray(upto, _I32)
        sid = upto >> self.shard_bits
        off = upto & (self.shard_size - 1)

        def one(t, s, o):
            return self.shard_counts[s, t] + wm_rank(self.shard(s), t, o)

        flat = jax.vmap(one)(token.reshape(-1), sid.reshape(-1),
                             off.reshape(-1))
        return flat.reshape(token.shape)

    def locate(self, token: jax.Array, k: jax.Array) -> jax.Array:
        """Position of the k-th (0-based) occurrence of ``token``."""
        token = jnp.asarray(token, _I32)
        k = jnp.asarray(k, _I32)

        def one(t, kk):
            col = self.shard_counts[:, t]                  # (S+1,) cumulative
            s = jnp.clip(jnp.searchsorted(col, kk, side="right") - 1,
                         0, self.num_shards - 1)
            within = kk - col[s]
            return (s << self.shard_bits) + wm_select(self.shard(s), t, within)

        flat = jax.vmap(one)(token.reshape(-1), k.reshape(-1))
        return flat.reshape(token.shape)

    # ---- range analytics (repro.analytics engine over these shards) ----
    def range_quantile(self, lo, hi, k) -> jax.Array:
        """k-th smallest token in corpus positions [lo, hi). Batched."""
        return sharded_range_quantile(self.shards, self.shard_bits, self.n,
                                      lo, hi, k)

    def range_count(self, lo, hi, sym_lo, sym_hi) -> jax.Array:
        """# of positions in [lo, hi) holding a token in [sym_lo, sym_hi)."""
        return sharded_range_count(self.shards, self.shard_bits, self.n,
                                   lo, hi, sym_lo, sym_hi)

    def range_topk(self, lo, hi, k: int):
        """(tokens, counts) of the k most frequent tokens in [lo, hi)."""
        return sharded_range_topk(self.shards, self.shard_bits, self.n,
                                  lo, hi, k)

    def range_distinct(self, lo, hi) -> jax.Array:
        """# of distinct tokens in [lo, hi)."""
        return sharded_range_distinct(self.shards, self.shard_bits, self.n,
                                      lo, hi)

    def range_histogram(self, lo, hi) -> jax.Array:
        """Per-token counts over [lo, hi): (…, 2^nbits) int32."""
        return sharded_range_histogram(self.shards, self.shard_bits, self.n,
                                       lo, hi)


def build_compressed_corpus(tokens: np.ndarray, sigma: int,
                            shard_bits: int = 16, tau: int = 8,
                            big_step: str = "compose",
                            sample_rate: int = 512,
                            parallel: str | bool = "auto"
                            ) -> CompressedCorpus:
    """Ingest a token stream: pad to whole shards, run the paper's parallel
    construction per shard, stack the shard trees leaf-wise. Shard builds
    fan out over the device mesh (``data.shard_build``): pmap across
    devices when several are present, else a vmap or the sequential loop
    per ``parallel`` ("auto" | True | False).

    Padding tokens (id 0) exist only in the slack tail of the last shard
    and are never addressed (n records the true length; the shard
    histograms subtract them).

    Host spans: ``store.build`` (attributes ``shards``, ``sigma``,
    ``mode``) over the shard builds (``data.shard_build``'s spans) and
    ``store.build.histogram`` (the per-shard ``bincount``, the cumsum and
    the upload of ``shard_counts``); none waits for the device.
    """
    n = int(len(tokens))
    shard_size = 1 << shard_bits
    num_shards = max(1, (n + shard_size - 1) // shard_size)
    with obs.span("store.build", shards=num_shards, sigma=sigma,
                  mode=build_mode(num_shards, parallel)):
        pad = num_shards * shard_size - n
        toks = np.asarray(tokens, np.uint32)
        if pad:
            toks = np.concatenate([toks, np.zeros(pad, np.uint32)])
        shards_np = toks.reshape(num_shards, shard_size)

        def wm_shard_build(s):
            return build_wavelet_matrix(s, sigma, tau=tau, big_step=big_step,
                                        sample_rate=sample_rate)

        # jit_loop compiles the whole builder once on the sequential path
        # so every shard reuses one executable; the named function names
        # the program (jit_wm_shard_build, pmap_wm_shard_build).
        stacked = build_shards_stacked(wm_shard_build, shards_np,
                                       parallel=parallel, jit_loop=True)

        with obs.span("store.build.histogram"):
            hist = np.zeros((num_shards, sigma), np.int64)
            for i, s in enumerate(shards_np):
                hist[i] = np.bincount(s, minlength=sigma)[:sigma]
            if pad:  # padding tokens are id 0: remove them from the last
                hist[-1, 0] -= pad
            cum = np.concatenate([np.zeros((1, sigma), np.int64),
                                  np.cumsum(hist, axis=0)]).astype(np.int32)
            shard_counts = jnp.asarray(cum)

    return CompressedCorpus(shards=stacked, shard_counts=shard_counts,
                            n=n, sigma=sigma, shard_bits=shard_bits)


def token_histogram(corpus: CompressedCorpus) -> jax.Array:
    """Global symbol frequencies (drives Huffman codebooks, sampling)."""
    return corpus.shard_counts[-1]
