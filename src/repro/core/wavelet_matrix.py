"""Wavelet matrix construction (paper Section 4, Theorem 4.5) and queries.

The wavelet matrix [Claude & Navarro] stores one n-bit bitmap per level; at
level l all symbols whose l-th highest bit is 0 move (stably) to the left and
the rest to the right. The paper constructs it in τ-bit chunks: every τ-th
level is produced by ONE stable integer sort keyed on the *reverse* of the
next τ bits, and the τ−1 levels in between are derived from packed τ-bit
"short lists".

TPU realization (DESIGN.md §2): the short lists become narrow (uint8) key
arrays; each in-between level is a stable 0/1 partition of the narrow array
(two prefix sums); the big-level sort is either (a) the *composition* of the
τ partition permutations applied once to the full-width symbols
(``big_step="compose"``, paper-faithful prefix-sum-only data flow), (b) a
direct stable counting sort on the reversed τ-bit key (``"radix"``), or
(c) XLA's stable sort (``"xla"``). Full-width symbols move only once per τ
levels — the τ-fold traffic saving that the paper's work bound expresses.

Construction fast path (default, ``fused=True``): each in-chunk level is
applied as a *gather* whose permutation comes from the select formulation
of the stable partition (``rank_select.stable_partition_gather`` — the
Theorem 5.1 word-rank/select directory, built per level in O(n/log n)
work, answers "which element lands at position p"), the composed
permutation advances only when a compose big step will consume it, and all
``nbits`` rank/select directories are built as one batched launch group
(``rank_select.build_bitvector_levels``). With ``use_kernels=True`` the
per-level step and the batched rank tables route through the Pallas
kernels ``kernels.wm_level`` (bit extract + bitmap pack + zero count +
stable destinations in a single launch over the narrow short list) and
``kernels.rank_build`` (all levels' Jacobson tables in one launch). That
route is opt-in: the kernels do not compile for a TPU v5e yet, so every
backend builds through XLA by default. Outputs are bit-identical on every
path.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from . import bitops
from .rank_select import (BitVector, access_bit, build_bitvector,
                          build_bitvector_levels, rank0, rank1, select0,
                          select1, stable_partition_gather)
from .scan import apply_permutation_dest, stable_partition_indices
from .sort import _invert_permutation, sort_pass

_U32 = jnp.uint32


def num_levels(sigma: int) -> int:
    return max(1, math.ceil(math.log2(max(2, sigma))))


def reverse_bits(x: jax.Array, width: int) -> jax.Array:
    """Reverse the low ``width`` bits of each element."""
    x = x.astype(_U32)
    out = jnp.zeros_like(x)
    for i in range(width):
        out = out | (((x >> _U32(i)) & _U32(1)) << _U32(width - 1 - i))
    return out


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class WaveletMatrix:
    """Per-level bitvectors stacked on a leading (nbits,) axis."""
    bitvectors: BitVector   # every leaf carries a leading (nbits,) axis
    zeros: jax.Array        # (nbits,) int32 — zeros per level
    n: int = field(metadata=dict(static=True))
    nbits: int = field(metadata=dict(static=True))

    def level(self, l: int) -> BitVector:
        return jax.tree.map(lambda x: x[l], self.bitvectors)


def _pack_level(bit: jax.Array) -> jax.Array:
    return bitops.pack_bits(bitops.pad_bits(bit.astype(jnp.uint8)))


def build_wavelet_matrix(seq: jax.Array, sigma: int, tau: int = 8,
                         big_step: str = "compose",
                         sample_rate: int = 512,
                         fused: bool = True,
                         use_kernels: bool = False) -> WaveletMatrix:
    """τ-chunked parallel construction (paper Theorem 4.5).

    ``tau`` plays the paper's τ = √(log n) role; 8 (byte-aligned) is the TPU
    sweet spot (DESIGN.md §2 assumption 4). ``big_step`` selects how the
    every-τ-levels reshuffle of the full-width symbols is realized.

    ``fused=True`` (default) takes the construction fast path: the
    per-level stable partition is applied as a *gather* whose permutation
    comes from the paper's own select machinery
    (``rank_select.stable_partition_gather`` — Theorem 5.1 structures
    driving the Theorem 4.5 build), the composed permutation is carried
    only when a compose big step actually needs it, and all ``nbits``
    rank/select directories are built in one batched launch group
    (``build_bitvector_levels``). ``fused=False`` is the historical XLA
    step-by-step path (scatter-based inverse permutations, per-level
    directory builds) kept as the benchmark baseline.

    ``use_kernels=True`` routes the per-level step and the batched rank
    tables through the Pallas kernels (``kernels.wm_level`` /
    ``kernels.rank_build``). It is off by default on every backend: neither
    kernel compiles for a TPU v5e (block shapes not (8,128)-aligned), so
    they run only in interpret mode, as a reference for the tests. They
    carry cross-grid scratch state and must not be batched.

    Output is bit-identical across ``fused``/``use_kernels``/``big_step``
    settings (and to ``build_wavelet_matrix_levelwise``).
    """
    from repro import obs
    obs.counter("core.build", builder="wm",
                path="fused" if fused else "scatter").inc()
    if not fused:
        return _build_wavelet_matrix_steps(seq, sigma, tau, big_step,
                                           sample_rate)

    n = int(seq.shape[0])
    nbits = num_levels(sigma)
    order = seq.astype(_U32)
    level_words: List[jax.Array] = []
    zeros: List[jax.Array] = []

    for alpha0 in range(0, nbits, tau):
        width = min(tau, nbits - alpha0)
        # τ-bit field starting at bit-offset alpha0 from the top.
        fld = bitops.extract_field(order, jnp.uint32(nbits - alpha0 - width),
                                   width)
        sub = fld                       # narrow working array ("short list")
        last_chunk = alpha0 + width >= nbits
        # The composed permutation is materialized only when the compose
        # big step will consume it (the historical path carried it always).
        need_idx = (not last_chunk) and big_step == "compose"
        idx = jnp.arange(n, dtype=jnp.int32) if need_idx else None
        for t in range(width):
            shift = width - 1 - t
            last_level = (alpha0 + t == nbits - 1)
            # Movement is needed to arrange the *next* level's bitmap; at
            # the chunk's final level only the composed permutation (if
            # any) still advances — radix/xla big steps re-sort from the
            # chunk-start order and subsume it.
            move = (not last_level) and (t < width - 1 or need_idx)
            obs.counter("core.level_step", builder="wm",
                        impl="kernel" if use_kernels else "xla").inc()
            if use_kernels:
                from repro.kernels import ops as _kops
                dest, words, z = _kops.wm_level_step_fused(sub, shift, n)
                level_words.append(words)
                zeros.append(z)
                if move:
                    if t < width - 1:
                        sub = apply_permutation_dest(sub, dest)
                    if need_idx:
                        idx = apply_permutation_dest(idx, dest)
            else:
                bit = (sub >> _U32(shift)) & _U32(1)
                words = _pack_level(bit)
                z = jnp.int32(n) - jnp.sum(bit, dtype=jnp.int32)
                level_words.append(words)
                zeros.append(z)
                if move:
                    g = stable_partition_gather(words, z, n)
                    if t < width - 1:
                        sub = sub[g]
                    if need_idx:
                        idx = idx[g]
        if not last_chunk:
            if big_step == "compose":
                order = order[idx]
            elif big_step in ("radix", "xla"):
                rev = reverse_bits(fld, width)
                backend = "counting" if big_step == "radix" else "xla"
                order, _ = sort_pass(order, rev, 1 << width, backend=backend)
            else:
                raise ValueError(f"unknown big_step {big_step!r}")

    stacked = build_bitvector_levels(jnp.stack(level_words), n, sample_rate,
                                     use_kernels=use_kernels)
    return WaveletMatrix(bitvectors=stacked, zeros=jnp.stack(zeros),
                         n=n, nbits=nbits)


def _build_wavelet_matrix_steps(seq: jax.Array, sigma: int, tau: int = 8,
                                big_step: str = "compose",
                                sample_rate: int = 512) -> WaveletMatrix:
    """Historical step-by-step XLA realization of Theorem 4.5 (benchmark
    baseline for the fused fast path): per-level scatter-based inverse
    permutations, unconditionally composed permutation, per-level
    directory builds."""
    n = int(seq.shape[0])
    nbits = num_levels(sigma)
    order = seq.astype(_U32)
    level_words: List[jax.Array] = []
    zeros: List[jax.Array] = []

    for alpha0 in range(0, nbits, tau):
        width = min(tau, nbits - alpha0)
        fld = bitops.extract_field(order, jnp.uint32(nbits - alpha0 - width),
                                   width)
        sub = fld
        perm = None                     # composed gather permutation
        for t in range(width):
            bit = (sub >> _U32(width - 1 - t)) & _U32(1)
            level_words.append(_pack_level(bit))
            zeros.append(jnp.int32(n) - jnp.sum(bit, dtype=jnp.int32))
            last_level = (alpha0 + t == nbits - 1)
            if not last_level:
                dest = stable_partition_indices(bit)
                g = _invert_permutation(dest)
                sub = sub[g]
                perm = g if perm is None else perm[g]
        if alpha0 + width < nbits:
            if big_step == "compose":
                order = order[perm]
            elif big_step in ("radix", "xla"):
                rev = reverse_bits(fld, width)
                backend = "counting" if big_step == "radix" else "xla"
                order, _ = sort_pass(order, rev, 1 << width, backend=backend)
            else:
                raise ValueError(f"unknown big_step {big_step!r}")

    bvs = [build_bitvector(w, n, sample_rate) for w in level_words]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *bvs)
    return WaveletMatrix(bitvectors=stacked, zeros=jnp.stack(zeros),
                         n=n, nbits=nbits)


def build_wavelet_matrix_levelwise(seq: jax.Array, sigma: int,
                                   sample_rate: int = 512) -> WaveletMatrix:
    """Prior-work baseline [Shun'15]: O(n·logσ) work, full-width symbols
    permuted at every level. Kept for the benchmarks' before/after rows."""
    from repro import obs
    obs.counter("core.build", builder="wm_levelwise", path="scatter").inc()
    n = int(seq.shape[0])
    nbits = num_levels(sigma)
    order = seq.astype(_U32)
    level_words, zeros = [], []
    for l in range(nbits):
        bit = (order >> _U32(nbits - 1 - l)) & _U32(1)
        level_words.append(_pack_level(bit))
        zeros.append(jnp.int32(n) - jnp.sum(bit, dtype=jnp.int32))
        if l < nbits - 1:
            dest = stable_partition_indices(bit)
            order = order[_invert_permutation(dest)]
    bvs = [build_bitvector(w, n, sample_rate) for w in level_words]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *bvs)
    return WaveletMatrix(bitvectors=stacked, zeros=jnp.stack(zeros),
                         n=n, nbits=nbits)


# --------------------------------------------------------------------------
# Level-descent primitives (shared by queries here and repro.analytics)
# --------------------------------------------------------------------------

def wm_interval_zeros(wm: WaveletMatrix, l: int, lo: jax.Array,
                      hi: jax.Array):
    """rank0 at both ends of [lo, hi) on level ``l``: (zeros before lo,
    zeros before hi). The zero count *inside* the interval is the
    difference — the quantity every range query branches on."""
    bv = wm.level(l)
    return rank0(bv.rank, lo), rank0(bv.rank, hi)


def wm_child_interval(wm: WaveletMatrix, l: int, lo: jax.Array,
                      hi: jax.Array, bit: jax.Array,
                      lo0: jax.Array = None, hi0: jax.Array = None):
    """Map interval [lo, hi) at level ``l`` to its child interval under
    ``bit`` (0 → left/zero block, 1 → right/one block). Pass ``lo0``/``hi0``
    (rank0 at the endpoints) when already computed to avoid re-ranking."""
    if lo0 is None or hi0 is None:
        lo0, hi0 = wm_interval_zeros(wm, l, lo, hi)
    lo1 = wm.zeros[l] + (lo - lo0)
    hi1 = wm.zeros[l] + (hi - hi0)
    return (jnp.where(bit == 0, lo0, lo1),
            jnp.where(bit == 0, hi0, hi1))


def wm_position_step(wm: WaveletMatrix, l: int, p: jax.Array):
    """Follow one position down a level: (bit at p, position in child)."""
    bv = wm.level(l)
    bit = access_bit(bv.rank, p)
    child = jnp.where(bit == 0, rank0(bv.rank, p),
                      wm.zeros[l] + rank1(bv.rank, p))
    return bit, child


# --------------------------------------------------------------------------
# Queries
# --------------------------------------------------------------------------

def wm_access_descend(wm: WaveletMatrix, i: jax.Array):
    """(symbol c at position i, position where i's descent ends).

    The end position is ``wm_descend(wm, c, i)``, so an LF step reads it
    here instead of ranking c again. Vectorized over i."""
    i = jnp.asarray(i, jnp.int32)
    c = jnp.zeros_like(i)
    p = i
    for l in range(wm.nbits):
        bit, p = wm_position_step(wm, l, p)
        c = (c << 1) | bit
    return c, p


def wm_access(wm: WaveletMatrix, i: jax.Array) -> jax.Array:
    """Symbol at position i. Vectorized over i; O(logσ) rank calls."""
    return wm_access_descend(wm, i)[0]


def wm_descend(wm: WaveletMatrix, c: jax.Array, i: jax.Array) -> jax.Array:
    """Follow position i down every level along c's bits: one ``rank1``
    per level. The end is ``wm_block_starts(...)[c] + wm_rank(wm, c, i)``:
    the last level holds c's occurrences before i right after the start
    of c's block. Vectorized."""
    c = jnp.asarray(c, jnp.int32)
    p = jnp.asarray(i, jnp.int32)
    for l in range(wm.nbits):
        bit = (c >> (wm.nbits - 1 - l)) & 1
        ones = rank1(wm.level(l).rank, p)
        p = jnp.where(bit == 0, p - ones, wm.zeros[l] + ones)
    return p


def wm_block_starts(counts: jax.Array, nbits: int) -> jax.Array:
    """Row where each code's block starts after the last level, from the
    symbol counts alone: ``wm_descend(wm, c, 0)`` for every code c.

    The last level holds the symbols stably sorted by their bit-reversed
    code, so the start of c's block is the count of symbols whose
    reversed code is smaller. ``counts`` is indexed by code (0 for codes
    that never occur); a code is read through its low ``nbits`` bits, as
    the descent reads it. The order is static, made once on the host.
    """
    code = np.arange(counts.shape[0]) & ((1 << nbits) - 1)
    rev = np.zeros_like(code)
    for b in range(nbits):
        rev |= ((code >> b) & 1) << (nbits - 1 - b)
    order = np.argsort(rev, kind="stable")
    # codes that read as the same nbits share a start: count what is
    # strictly smaller
    first = np.searchsorted(rev[order], rev, side="left")
    ordered = jnp.asarray(counts, jnp.int32)[order]
    return (jnp.cumsum(ordered) - ordered)[first]


def wm_rank(wm: WaveletMatrix, c: jax.Array, i: jax.Array) -> jax.Array:
    """# of occurrences of symbol c in [0, i). Vectorized."""
    c = jnp.asarray(c, jnp.int32)
    i = jnp.asarray(i, jnp.int32)
    lo = jnp.zeros_like(i)
    hi = i
    for l in range(wm.nbits):
        bit = (c >> (wm.nbits - 1 - l)) & 1
        lo, hi = wm_child_interval(wm, l, lo, hi, bit)
    return hi - lo


def wm_select(wm: WaveletMatrix, c: jax.Array, k: jax.Array) -> jax.Array:
    """Position of the k-th (0-based) occurrence of c. Vectorized.

    Descend to find the start offset of c's block at the deepest level, then
    ascend converting block-relative ranks back to positions via select.
    Out-of-range ``k`` (≥ count of c, or c absent) returns a clamped
    position in [0, n) rather than garbage — callers that need to detect
    overflow should compare k against ``wm_rank(wm, c, n)`` first.
    """
    c = jnp.asarray(c, jnp.int32)
    k = jnp.asarray(k, jnp.int32)
    lo = jnp.zeros_like(k)
    for l in range(wm.nbits):
        bit = (c >> (wm.nbits - 1 - l)) & 1
        lo, _ = wm_child_interval(wm, l, lo, lo, bit)
    pos = lo + k
    for l in range(wm.nbits - 1, -1, -1):
        bv = wm.level(l)
        bit = (c >> (wm.nbits - 1 - l)) & 1
        pos = jnp.where(bit == 0,
                        select0(bv.rank, bv.sel0, pos),
                        select1(bv.rank, bv.sel1, pos - wm.zeros[l]))
        pos = jnp.clip(pos, 0, wm.n - 1)
    return pos
