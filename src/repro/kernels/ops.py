"""Jit'd public wrappers around the Pallas kernels.

Each op handles padding/layout, dispatches to the kernel, and trims the
result. ``interpret`` defaults to True off-TPU so the same call sites work
on CPU (validation) and TPU.

None of these kernels compiles for a TPU v5e yet: their block shapes are
not (8,128)-aligned (``bitpack`` also reduces over unsigned integers and
``wm_quantile`` needs a non-2D gather). So no default path calls them;
each is reached only through an explicit ``use_kernels``/``use_kernel``
option, and the tests run them in interpret mode against the XLA paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs as _obs

from . import bitpack as _bitpack
from . import radix_rank as _radix_rank
from . import rank_build as _rank_build
from . import wm_level as _wm_level
from . import wm_quantile as _wm_quantile
from . import wt_level as _wt_level


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _work_gauges(op: str, elements: int, bits: int | None = None) -> None:
    """Trace-time work counters: problem size per kernel launch.

    Shapes are static under jit, so these record the size of the *traced*
    launch (the same semantics as the ``kernels.trace`` counters). The
    profiling layer divides them by measured steady-state time to derive
    Melem/s (``prof.melem_per_s``)."""
    _obs.gauge("kernels.work.elements", op=op).set(float(elements))
    if bits is not None:
        _obs.gauge("kernels.work.bits", op=op).set(float(bits))


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitpack(bits: jax.Array, interpret: bool | None = None) -> jax.Array:
    """Pack a (n,) 0/1 vector into ceil(n/32) uint32 words (LSB-first)."""
    if interpret is None:
        interpret = _default_interpret()
    _obs.counter("kernels.trace", op="bitpack",
                 interpret=str(bool(interpret)).lower()).inc()
    n = bits.shape[0]
    _work_gauges("bitpack", n, bits=n)
    w = (n + 31) // 32
    wpad = ((w + _bitpack.LANES - 1) // _bitpack.LANES) * _bitpack.LANES
    flat = jnp.zeros((wpad * 32,), jnp.int32).at[:n].set(bits.astype(jnp.int32))
    bits_t = flat.reshape(wpad, 32).T                     # (32, wpad)
    words = _bitpack.bitpack_pallas(bits_t, interpret=interpret)
    return words[0, :w]


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def rank_build(words: jax.Array, n: int,
               interpret: bool | None = None):
    """Jacobson directory for a packed bit sequence of n bits.

    Returns (superblock uint32 (ceil(W/32),), block_rel uint16 (ceil(W/4),)),
    W = ceil(n/32) — identical contract to
    ``repro.core.rank_select.build_binary_rank``.
    """
    if interpret is None:
        interpret = _default_interpret()
    _obs.counter("kernels.trace", op="rank_build",
                 interpret=str(bool(interpret)).lower()).inc()
    _work_gauges("rank_build", n, bits=n)
    w = (n + 31) // 32
    sw = _rank_build.STEP_WORDS
    wpad = ((w + sw - 1) // sw) * sw
    wp = jnp.zeros((1, wpad), jnp.uint32).at[0, :words.shape[0]].set(words)
    block_rel, superblock = _rank_build.rank_build_pallas(
        wp, interpret=interpret)
    nsb = (w + _rank_build.SUPERBLOCK_WORDS - 1) // _rank_build.SUPERBLOCK_WORDS
    nblk = (w + _rank_build.BLOCK_WORDS - 1) // _rank_build.BLOCK_WORDS
    return superblock[0, :nsb], block_rel[0, :nblk]


@functools.partial(jax.jit, static_argnames=("shift", "n", "interpret"))
def wm_level_step(sub: jax.Array, shift: int, n: int,
                  interpret: bool | None = None):
    """One fused wavelet-matrix level on narrow keys ``sub`` (n,).

    ``shift``: bit position of this level's bit inside the key.
    Returns (dest (n,) int32 stable-partition destinations,
             bitmap ceil(n/32) uint32, total_zeros scalar int32).
    """
    if interpret is None:
        interpret = _default_interpret()
    _obs.counter("kernels.trace", op="wm_level_step",
                 interpret=str(bool(interpret)).lower()).inc()
    _work_gauges("wm_level_step", n, bits=n)
    blk = _wm_level.BLOCK
    npad = ((n + blk - 1) // blk) * blk
    # pad with all-ones keys: they partition past n and are trimmed
    pad_val = jnp.uint32(1) << jnp.uint32(shift)
    sp = jnp.full((1, npad), pad_val, jnp.uint32).at[0, :n].set(
        sub.astype(jnp.uint32))
    zeros_per_block = _wm_level.wm_counts_pallas(sp, shift,
                                                 interpret=interpret)
    zexcl = (jnp.cumsum(zeros_per_block, axis=1) - zeros_per_block)
    total = jnp.sum(zeros_per_block, dtype=jnp.int32).reshape(1, 1)
    dest, bitmap = _wm_level.wm_apply_pallas(sp, zexcl, total, shift, n,
                                             interpret=interpret)
    wreal = (n + 31) // 32
    return dest[0, :n], bitmap[0, :wreal], total[0, 0]


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def rank_build_levels(words: jax.Array, n: int,
                      interpret: bool | None = None):
    """Batched Jacobson directories for L stacked level bitmaps, one
    launch. ``words``: (L, W) uint32 packed bits (n bits per row).

    Returns (superblock uint32 (L, ceil(W/32)), block_rel uint16
    (L, ceil(W/4))) — row-wise identical to ``rank_build``.
    """
    if interpret is None:
        interpret = _default_interpret()
    _obs.counter("kernels.trace", op="rank_build_levels",
                 interpret=str(bool(interpret)).lower()).inc()
    nlev = words.shape[0]
    _work_gauges("rank_build_levels", nlev * n, bits=nlev * n)
    w = (n + 31) // 32
    sw = _rank_build.STEP_WORDS
    wpad = ((w + sw - 1) // sw) * sw
    wp = jnp.zeros((nlev, wpad), jnp.uint32).at[:, :words.shape[1]].set(words)
    block_rel, superblock = _rank_build.rank_build_levels_pallas(
        wp, interpret=interpret)
    nsb = (w + _rank_build.SUPERBLOCK_WORDS - 1) // _rank_build.SUPERBLOCK_WORDS
    nblk = (w + _rank_build.BLOCK_WORDS - 1) // _rank_build.BLOCK_WORDS
    return superblock[:, :nsb], block_rel[:, :nblk]


@functools.partial(jax.jit, static_argnames=("shift", "n", "interpret"))
def wm_level_step_fused(sub: jax.Array, shift: int, n: int,
                        interpret: bool | None = None):
    """Single-launch fused wavelet-matrix level (tentpole form of
    ``wm_level_step``): bit extract, bitmap pack, zero count and stable
    partition destinations in ONE kernel launch over the narrow short
    list. Same contract as ``wm_level_step``. Not vmap-safe (cross-grid
    scratch) — batched builders use the XLA fast path instead.
    """
    if interpret is None:
        interpret = _default_interpret()
    _obs.counter("kernels.trace", op="wm_level_step_fused",
                 interpret=str(bool(interpret)).lower()).inc()
    _work_gauges("wm_level_step_fused", n, bits=n)
    blk = _wm_level.BLOCK
    npad = ((n + blk - 1) // blk) * blk
    pad_val = jnp.uint32(1) << jnp.uint32(shift)
    sp = jnp.full((1, npad), pad_val, jnp.uint32).at[0, :n].set(
        sub.astype(jnp.uint32))
    dest, bitmap, total = _wm_level.wm_level_fused_pallas(
        sp, shift, n, interpret=interpret)
    wreal = (n + 31) // 32
    return dest[0, :n], bitmap[0, :wreal], total[0, 0]


@functools.partial(jax.jit, static_argnames=("shift", "nbkt", "n",
                                             "interpret"))
def wt_level_step_fused(sub: jax.Array, nid: jax.Array, shift: int,
                        nbkt: int, n: int, interpret: bool | None = None):
    """One fused *segmented* wavelet-tree level on narrow keys (n,).

    ``nid``: (n,) int32 node id per element (non-decreasing), ``shift``:
    bit position of this level's bit inside the key, ``nbkt`` = 2^(l+1)
    the (node, bit) bucket count (≤ ``wt_level.MAX_KEYS``). Returns
    (dest (n,) int32 stable per-node partition destinations,
    bitmap ceil(n/32) uint32). Not vmap-safe (cross-grid scratch).
    """
    if interpret is None:
        interpret = _default_interpret()
    _obs.counter("kernels.trace", op="wt_level_step_fused",
                 interpret=str(bool(interpret)).lower()).inc()
    _work_gauges("wt_level_step_fused", n, bits=n)
    blk = _wt_level.BLOCK
    npad = ((n + blk - 1) // blk) * blk
    # padding: bit 0 + nid nbkt//2 -> key == nbkt, the sentinel bucket
    # ordered after every real bucket (destinations land past n, trimmed)
    sp = jnp.zeros((1, npad), jnp.uint32).at[0, :n].set(
        sub.astype(jnp.uint32))
    nidp = jnp.full((1, npad), nbkt // 2, jnp.int32).at[0, :n].set(
        nid.astype(jnp.int32))
    dest, bitmap = _wt_level.wt_level_fused_pallas(
        sp, nidp, shift, nbkt, n, interpret=interpret)
    wreal = (n + 31) // 32
    return dest[0, :n], bitmap[0, :wreal]


@functools.partial(jax.jit, static_argnames=("num_buckets", "interpret"))
def radix_rank(digits: jax.Array, num_buckets: int,
               interpret: bool | None = None) -> jax.Array:
    """Blocked stable counting rank (Pallas): destination of every element
    under a stable sort by ``digits`` (each in [0, num_buckets),
    num_buckets ≤ ``radix_rank.MAX_BUCKETS``). Same contract as
    ``core.sort.counting_rank``; the per-block one-hot lives only in VMEM.
    """
    assert num_buckets <= _radix_rank.MAX_BUCKETS
    if interpret is None:
        interpret = _default_interpret()
    _obs.counter("kernels.trace", op="radix_rank",
                 interpret=str(bool(interpret)).lower()).inc()
    n = digits.shape[0]
    _work_gauges("radix_rank", n)
    blk = _radix_rank.BLOCK
    npad = ((n + blk - 1) // blk) * blk
    d = jnp.full((1, npad), num_buckets, jnp.int32).at[0, :n].set(
        digits.astype(jnp.int32))
    hist = _radix_rank.radix_hist_pallas(d, num_buckets,
                                         interpret=interpret)
    across = jnp.cumsum(hist, axis=0, dtype=jnp.int32) - hist
    totals = jnp.sum(hist, axis=0, dtype=jnp.int32)
    base = (jnp.cumsum(totals) - totals).reshape(1, -1)
    dest = _radix_rank.radix_apply_pallas(d, base, across, num_buckets,
                                          interpret=interpret)
    return dest[0, :n]


def _pad_axis1(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[1]) % mult
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((x.shape[0], pad), x.dtype)], axis=1)
    return x


def _pad_rank_rows(words: jax.Array, superblock: jax.Array,
                   block: jax.Array, nblocks: int):
    """Lane-pad the row-stacked rank-directory arrays for the quantile
    kernels: word rows grow to ≥ nblocks·BLOCK_WORDS (so every directory
    block can gather all its words) and everything pads to 128 lanes."""
    words = _pad_axis1(words, 128)
    if words.shape[1] < nblocks * _wm_quantile.BLOCK_WORDS:
        words = _pad_axis1(
            jnp.concatenate(
                [words, jnp.zeros((words.shape[0],
                                   nblocks * _wm_quantile.BLOCK_WORDS
                                   - words.shape[1]), words.dtype)],
                axis=1), 128)
    return (words, _pad_axis1(superblock, 128),
            _pad_axis1(block.astype(jnp.int32), 128))


@functools.partial(jax.jit, static_argnames=("interpret",))
def wm_quantile_batch(wm, lo: jax.Array, hi: jax.Array, k: jax.Array,
                      interpret: bool | None = None) -> jax.Array:
    """Batched range-quantile over one ``WaveletMatrix`` via the fused
    Pallas level-descent kernel (all nbits levels in one launch).

    ``lo``/``hi``/``k``: (Q,) int32. Returns (Q,) int32 symbols, -1 for
    empty ranges (same contract as ``repro.analytics.range_quantile``).
    """
    if interpret is None:
        interpret = _default_interpret()
    _obs.counter("kernels.trace", op="wm_quantile_batch",
                 interpret=str(bool(interpret)).lower()).inc()
    lo = jnp.atleast_1d(jnp.asarray(lo, jnp.int32))
    hi = jnp.atleast_1d(jnp.asarray(hi, jnp.int32))
    k = jnp.atleast_1d(jnp.asarray(k, jnp.int32))
    q = lo.shape[0]
    _work_gauges("wm_quantile_batch", q)
    qpad = ((q + _wm_quantile.QBLOCK - 1)
            // _wm_quantile.QBLOCK) * _wm_quantile.QBLOCK
    queries = jnp.zeros((3, qpad), jnp.int32)
    queries = queries.at[0, :q].set(lo).at[1, :q].set(hi).at[2, :q].set(k)

    rank = wm.bitvectors.rank                 # leaves carry (nbits,) axis
    nblocks = rank.block.shape[1]
    words, superblock, block = _pad_rank_rows(rank.words, rank.superblock,
                                              rank.block, nblocks)
    zeros = wm.zeros.reshape(1, -1)
    out = _wm_quantile.wm_quantile_pallas(
        queries, words, superblock, block, zeros,
        n=wm.n, nblocks=nblocks, interpret=interpret)
    return out[0, :q]


@functools.partial(jax.jit, static_argnames=("shard_bits", "n", "interpret"))
def wm_quantile_sharded_batch(shards, shard_bits: int, n: int,
                              lo: jax.Array, hi: jax.Array, k: jax.Array,
                              interpret: bool | None = None) -> jax.Array:
    """Batched global range-quantile over a stacked (S,)-leaf shard layout
    via the fused sharded Pallas descent (all shards × all levels in one
    launch per query block).

    ``shards``: a ``WaveletMatrix`` whose leaves carry a leading
    (num_shards,) axis (the ``ShardedAnalytics``/``CompressedCorpus``
    layout); ``lo``/``hi``/``k``: (Q,) int32 *global* positions / rank.
    Exact same contract as ``analytics.engine.sharded_range_quantile``.
    """
    if interpret is None:
        interpret = _default_interpret()
    _obs.counter("kernels.trace", op="wm_quantile_sharded_batch",
                 interpret=str(bool(interpret)).lower()).inc()
    lo = jnp.atleast_1d(jnp.asarray(lo, jnp.int32))
    hi = jnp.atleast_1d(jnp.asarray(hi, jnp.int32))
    k = jnp.atleast_1d(jnp.asarray(k, jnp.int32))
    q = lo.shape[0]
    _work_gauges("wm_quantile_sharded_batch", q)
    qpad = ((q + _wm_quantile.QBLOCK - 1)
            // _wm_quantile.QBLOCK) * _wm_quantile.QBLOCK
    queries = jnp.zeros((3, qpad), jnp.int32)
    queries = queries.at[0, :q].set(lo).at[1, :q].set(hi).at[2, :q].set(k)

    rank = shards.bitvectors.rank
    num_shards, nbits = rank.words.shape[0], shards.nbits
    nblocks = rank.block.shape[2]
    words, superblock, block = _pad_rank_rows(
        rank.words.reshape(num_shards * nbits, -1),
        rank.superblock.reshape(num_shards * nbits, -1),
        rank.block.reshape(num_shards * nbits, -1), nblocks)
    zeros = shards.zeros.reshape(1, num_shards * nbits)
    out = _wm_quantile.wm_quantile_sharded_pallas(
        queries, words, superblock, block, zeros,
        num_shards=num_shards, nbits=nbits, n=n, shard_bits=shard_bits,
        nblocks=nblocks, interpret=interpret)
    return out[0, :q]
