"""Parallel wavelet tree construction (paper Section 4, Theorems 4.1–4.2).

Levelwise layout: level l stores one n-bit bitmap — the concatenation of all
node bitmaps at depth l, with the sequence stably sorted by the top-l bits of
each symbol (so node bitmaps are contiguous). ``node_starts[l][v]`` gives the
offset of node v (= a top-l-bit prefix) in that bitmap.

Three constructions, mirroring the paper's Table 1 rows:

* ``build_wavelet_tree``            — the τ-chunked sort-based algorithm
  (Theorem 4.1). Big-node levels every τ are produced by a stable integer
  sort of the full-width symbols; in-between levels operate on narrow
  ("short list") τ-bit keys with *node-segmented* stable partitions built
  from prefix sums. ``big_step`` chooses compose/radix/xla as in the
  wavelet matrix (see wavelet_matrix.py docstring).
* ``build_wavelet_tree_levelwise``  — prior-work baseline [Shun'15]:
  O(n logσ) work, full symbols reshuffled every level.
* ``build_wavelet_tree_dd``         — the domain-decomposition algorithm
  (Theorem 4.2): split into P chunks, build P trees in parallel (vmap), and
  merge per-node bitmaps with cross-chunk prefix-sum offsets.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List

import jax
import jax.numpy as jnp

from . import bitops
from .rank_select import (BitVector, access_bit, build_bitvector,
                          build_bitvector_levels, rank0, rank1,
                          segmented_partition_gather, select0, select1)
from .scan import (apply_permutation_dest, exclusive_sum,
                   segment_ids_from_starts, segmented_exclusive_sum)
from .sort import _invert_permutation, counting_rank, sort_pass
from .wavelet_matrix import num_levels

_U32 = jnp.uint32
_I32 = jnp.int32


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class WaveletTree:
    """Levelwise wavelet tree with per-level bitvectors + node offsets.

    ``node_starts`` has shape (nbits+1, 2**nbits): row l holds the starting
    offset of every depth-l node (only the first 2**l entries are
    meaningful); row nbits is the leaf (symbol) offset table — the C array.
    """
    bitvectors: BitVector    # every leaf carries a leading (nbits,) axis
    node_starts: jax.Array   # (nbits+1, 2**nbits) int32
    n: int = field(metadata=dict(static=True))
    nbits: int = field(metadata=dict(static=True))

    def level(self, l: int) -> BitVector:
        return jax.tree.map(lambda x: x[l], self.bitvectors)


def _node_starts_from_symbols(seq: jax.Array, nbits: int) -> jax.Array:
    """Offsets of every node at every level, from symbol counts alone.

    Node v at level l covers symbols [v<<(nbits-l), (v+1)<<(nbits-l)); its
    start is the count of smaller symbols — one histogram + one prefix sum
    (O(n + σ·logσ) work, O(log n) depth).
    """
    size = 1 << nbits
    hist = jnp.zeros((size,), _I32).at[seq.astype(_I32)].add(1, mode="drop")
    leaf_starts = exclusive_sum(hist)
    rows = [leaf_starts]
    for l in range(nbits - 1, -1, -1):
        width = 1 << (nbits - l)
        starts_l = leaf_starts[::width]                  # (2**l,)
        pad = jnp.zeros((size - starts_l.shape[0],), _I32)
        rows.append(jnp.concatenate([starts_l, pad]))
    rows.reverse()
    return jnp.stack(rows)                               # (nbits+1, size)


def _finalize(level_words: List[jax.Array], node_starts: jax.Array,
              n: int, nbits: int, sample_rate: int) -> WaveletTree:
    bvs = [build_bitvector(w, n, sample_rate) for w in level_words]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *bvs)
    return WaveletTree(bitvectors=stacked, node_starts=node_starts,
                       n=n, nbits=nbits)


def _finalize_fused(level_words: List[jax.Array], node_starts: jax.Array,
                    n: int, nbits: int, sample_rate: int,
                    use_kernels: bool = False) -> WaveletTree:
    """All nbits rank/select directories in one batched launch group —
    bit-identical to :func:`_finalize` (see build_bitvector_levels)."""
    stacked = build_bitvector_levels(jnp.stack(level_words), n, sample_rate,
                                     use_kernels=use_kernels)
    return WaveletTree(bitvectors=stacked, node_starts=node_starts,
                       n=n, nbits=nbits)


def _level_nid(node_starts: jax.Array, l: int, n: int) -> jax.Array:
    """Node id of every position at level l, from the offset table alone.

    After the level-(l−1) split the sequence is sorted by its top l bits,
    so membership is determined by the precomputed ``node_starts`` row —
    no per-element state needs to ride along the partitions.
    """
    if l == 0:
        return jnp.zeros((n,), _I32)
    return segment_ids_from_starts(node_starts[l][:1 << l], n)


def _pack_level(bit: jax.Array) -> jax.Array:
    return bitops.pack_bits(bitops.pad_bits(bit.astype(jnp.uint8)))


def _segmented_partition_dest(nid: jax.Array, bit: jax.Array,
                              level_plus1_bits: int) -> jax.Array:
    """Destination of each element under a stable per-node 0/1 partition.

    ``nid`` is the node id of each element (elements already grouped by
    node), ``bit`` the partition bit. Built from two segmented prefix sums
    plus a (node,bit) histogram — the paper's short-list split, with the
    packed-list table lookups replaced by scans (DESIGN.md §2).
    """
    n = nid.shape[0]
    key = (nid.astype(_I32) << 1) | bit.astype(_I32)
    nbuckets = 1 << level_plus1_bits
    hist = jnp.zeros((nbuckets,), _I32).at[key].add(1, mode="drop")
    key_start = exclusive_sum(hist)
    seg_start = jnp.concatenate([jnp.ones((1,), _I32),
                                 (nid[1:] != nid[:-1]).astype(_I32)])
    zeros_before = segmented_exclusive_sum(1 - bit.astype(_I32), seg_start)
    ones_before = segmented_exclusive_sum(bit.astype(_I32), seg_start)
    rank_within = jnp.where(bit == 0, zeros_before, ones_before)
    return key_start[key] + rank_within


def build_wavelet_tree(seq: jax.Array, sigma: int, tau: int = 8,
                       big_step: str = "compose",
                       sample_rate: int = 512,
                       fused: bool = True,
                       use_kernels: bool = False) -> WaveletTree:
    """τ-chunked sort-based construction (paper Theorem 4.1).

    ``fused=True`` (default) is the segmented select-gather fast path:
    each node-segmented stable partition is applied as a *gather* whose
    permutation comes from one word-granularity select directory
    (``rank_select.segmented_partition_gather``), node membership is
    re-derived per level from the precomputed ``node_starts`` table
    (a run-start mark + running max instead of a carried nid array), the
    composed permutation only materializes when a compose big step will
    consume it, and all nbits rank/select directories build in one
    batched launch group. ``fused=False`` keeps the historical scatter
    path (histogram + segmented scans + n-element inverse-permutation
    scatters) as the benchmark baseline. Outputs are bit-identical.

    ``use_kernels`` routes shallow levels (2^(l+1) key buckets within the
    ``kernels.wt_level`` VMEM bound) through the fused Pallas segmented
    level step and the directory builds through ``kernels.rank_build``.
    Off by default on every backend: neither kernel compiles for a TPU
    v5e, so they run only in interpret mode (see ``build_wavelet_matrix``).
    """
    from repro import obs
    obs.counter("core.build", builder="wt",
                path="fused" if fused else "scatter").inc()
    if not fused:
        return _build_wavelet_tree_steps(seq, sigma, tau, big_step,
                                         sample_rate)

    n = int(seq.shape[0])
    nbits = num_levels(sigma)
    node_starts = _node_starts_from_symbols(seq, nbits)
    order = seq.astype(_U32)
    level_words: List[jax.Array] = []

    for alpha0 in range(0, nbits, tau):
        width = min(tau, nbits - alpha0)
        fld = bitops.extract_field(order, jnp.uint32(nbits - alpha0 - width),
                                   width)
        sub = fld
        last_chunk = alpha0 + width >= nbits
        need_idx = (not last_chunk) and big_step == "compose"
        idx = jnp.arange(n, dtype=_I32) if need_idx else None
        for t in range(width):
            l = alpha0 + t
            shift = width - 1 - t
            last_level = l == nbits - 1
            # Movement only arranges the *next* level; at the chunk's last
            # level only a compose big step still consumes the permutation
            # (radix/xla re-sort from the chunk-start order).
            move = (not last_level) and (t < width - 1 or need_idx)
            words = None
            if move:
                nid = _level_nid(node_starts, l, n)
                kernel_ok = _wt_kernel_fits(l)
                if use_kernels and not kernel_ok:
                    # deep level: 2^(l+1) buckets exceed the wt_level VMEM
                    # bound — the gap ROADMAP item 4's deep-level kernel
                    # will close; count it so profiles show the fallback
                    obs.counter("core.wt_deep_fallback", level=l).inc()
                obs.counter("core.level_step", builder="wt",
                            impl="kernel" if use_kernels and kernel_ok
                            else "xla").inc()
                if use_kernels and kernel_ok:
                    from repro.kernels import ops as _kops
                    dest, words = _kops.wt_level_step_fused(
                        sub, nid, shift, 1 << (l + 1), n)
                    if t < width - 1:
                        sub = apply_permutation_dest(sub, dest)
                    if need_idx:
                        idx = apply_permutation_dest(idx, dest)
                else:
                    bit = ((sub >> _U32(shift)) & _U32(1)).astype(_I32)
                    words = _pack_level(bit)
                    g = segmented_partition_gather(
                        words, nid, node_starts[l][:1 << l], n)
                    if t < width - 1:
                        sub = sub[g]
                    if need_idx:
                        idx = idx[g]
            if words is None:
                bit = ((sub >> _U32(shift)) & _U32(1)).astype(_I32)
                words = _pack_level(bit)
            level_words.append(words)
        if not last_chunk:
            if big_step == "compose":
                order = order[idx]
            else:
                order = _tree_big_step(order, nbits, alpha0 + width,
                                       big_step)

    return _finalize_fused(level_words, node_starts, n, nbits, sample_rate,
                           use_kernels=use_kernels)


def _wt_kernel_fits(l: int) -> bool:
    from repro.kernels import wt_level as _wtk
    return (1 << (l + 1)) <= _wtk.MAX_KEYS


def _tree_big_step(order: jax.Array, nbits: int, consumed: int,
                   big_step: str) -> jax.Array:
    """One stable counting/XLA sort keyed on the top ``consumed`` bits —
    globally a sort by (node, next τ bits)."""
    key = (order >> _U32(nbits - consumed)).astype(_I32)
    if big_step == "radix":
        order, _ = sort_pass(order, key, 1 << consumed, backend="counting")
        return order
    if big_step == "xla":
        _, order = jax.lax.sort((key, order), num_keys=1, is_stable=True)
        return order
    raise ValueError(f"unknown big_step {big_step!r}")


def _build_wavelet_tree_steps(seq: jax.Array, sigma: int, tau: int = 8,
                              big_step: str = "compose",
                              sample_rate: int = 512) -> WaveletTree:
    """Historical step-by-step scatter realization of Theorem 4.1
    (benchmark baseline for the fused fast path)."""
    n = int(seq.shape[0])
    nbits = num_levels(sigma)
    node_starts = _node_starts_from_symbols(seq, nbits)
    order = seq.astype(_U32)
    level_words: List[jax.Array] = []

    for alpha0 in range(0, nbits, tau):
        width = min(tau, nbits - alpha0)
        fld = bitops.extract_field(order, jnp.uint32(nbits - alpha0 - width),
                                   width)
        nid = (order >> _U32(nbits - alpha0)).astype(_I32) if alpha0 else \
            jnp.zeros((n,), _I32)
        sub = fld
        perm = None
        for t in range(width):
            bit = ((sub >> _U32(width - 1 - t)) & _U32(1)).astype(_I32)
            level_words.append(_pack_level(bit))
            last_level = (alpha0 + t == nbits - 1)
            if not last_level:
                dest = _segmented_partition_dest(nid, bit, alpha0 + t + 1)
                g = _invert_permutation(dest)
                sub = sub[g]
                nid = ((nid << 1) | bit)[g]
                perm = g if perm is None else perm[g]
        if alpha0 + width < nbits:
            if big_step == "compose":
                order = order[perm]
            elif big_step in ("radix", "xla"):
                # one stable counting sort keyed on (node, next τ bits) —
                # globally this is a sort by the top (α+1)τ bits.
                key = (order >> _U32(nbits - alpha0 - width)).astype(_I32)
                if big_step == "radix":
                    dest = counting_rank(key, 1 << (alpha0 + width))
                    order = order[_invert_permutation(dest)]
                else:
                    _, order = jax.lax.sort((key, order), num_keys=1,
                                            is_stable=True)
            else:
                raise ValueError(f"unknown big_step {big_step!r}")

    return _finalize(level_words, node_starts, n, nbits, sample_rate)


def build_wavelet_tree_levelwise(seq: jax.Array, sigma: int,
                                 sample_rate: int = 512,
                                 fused: bool = True) -> WaveletTree:
    """Prior-work baseline [Shun'15]: O(n·logσ) work.

    ``fused=True`` applies each level's node-segmented partition as a
    select-gather (full-width symbols still move every level — the
    baseline's work bound is unchanged, only the scatter is gone).
    """
    from repro import obs
    obs.counter("core.build", builder="wt_levelwise",
                path="fused" if fused else "scatter").inc()
    n = int(seq.shape[0])
    nbits = num_levels(sigma)
    node_starts = _node_starts_from_symbols(seq, nbits)
    order = seq.astype(_U32)
    level_words = []
    for l in range(nbits):
        bit = ((order >> _U32(nbits - 1 - l)) & _U32(1)).astype(_I32)
        words = _pack_level(bit)
        level_words.append(words)
        if l < nbits - 1:
            if fused:
                nid = _level_nid(node_starts, l, n)
                g = segmented_partition_gather(
                    words, nid, node_starts[l][:1 << l], n)
                order = order[g]
            else:
                nid = (order >> _U32(nbits - l)).astype(_I32) if l else \
                    jnp.zeros((n,), _I32)
                dest = _segmented_partition_dest(nid, bit, l + 1)
                order = order[_invert_permutation(dest)]
    if fused:
        return _finalize_fused(level_words, node_starts, n, nbits,
                               sample_rate)
    return _finalize(level_words, node_starts, n, nbits, sample_rate)


# --------------------------------------------------------------------------
# Domain decomposition (paper Theorem 4.2)
# --------------------------------------------------------------------------

def build_wavelet_tree_dd(seq: jax.Array, sigma: int, num_chunks: int,
                          sample_rate: int = 512,
                          fused: bool = True) -> WaveletTree:
    """Domain-decomposition construction.

    The P per-chunk builds run under ``vmap`` (the paper's "P processors");
    the merge computes, for every (level, chunk, node), the destination
    offset ``global_node_start + Σ_{c'<c} len(c', node) + within`` with one
    cross-chunk prefix sum per level. ``fused=True`` (default) realizes
    both phases scatter-free: the per-chunk splits are segmented
    select-gathers (per-chunk node offsets sliced from one chunk
    histogram), and the merge becomes a *gather* — every (node, chunk)
    pair is one output run whose start is ``global_node_start[v] +
    across[c, v]``, so a run-start mark + running max assigns each output
    position its source chunk/offset directly (the paper's word-granular
    copy, with the boundary-word bookkeeping replaced by the mark trick).
    ``fused=False`` keeps the historical element-granular scatter merge.
    """
    from repro import obs
    obs.counter("core.build", builder="wt_dd",
                path="fused" if fused else "scatter").inc()
    n = int(seq.shape[0])
    assert n % num_chunks == 0, "pad the sequence to a multiple of num_chunks"
    m = n // num_chunks
    nbits = num_levels(sigma)
    size = 1 << nbits
    node_starts = _node_starts_from_symbols(seq, nbits)
    chunks = seq.reshape(num_chunks, m).astype(_U32)

    if fused:
        def chunk_build(chunk):
            """Per-chunk fused levelwise build: (nbits, m) bits + the
            chunk's symbol histogram (feeds the merge offsets)."""
            histc = jnp.zeros((size,), _I32).at[chunk.astype(_I32)].add(
                1, mode="drop")
            leafc = exclusive_sum(histc)
            order = chunk
            bits_out = []
            for l in range(nbits):
                bit = ((order >> _U32(nbits - 1 - l)) & _U32(1)).astype(_I32)
                bits_out.append(bit)
                if l < nbits - 1:
                    starts_l = leafc[:: 1 << (nbits - l)]       # (2**l,)
                    nid = segment_ids_from_starts(starts_l, m) if l else \
                        jnp.zeros((m,), _I32)
                    words = _pack_level(bit)
                    g = segmented_partition_gather(words, nid, starts_l, m)
                    order = order[g]
            return jnp.stack(bits_out), histc

        bits_all, hist_all = jax.vmap(chunk_build)(chunks)   # (P,nbits,m)
        csum = exclusive_sum(hist_all, axis=1)               # (P, size)
        p_out = jnp.arange(n, dtype=_I32)
        level_words = []
        for l in range(nbits):
            nodes_l = 1 << l
            sc = csum[:, :: 1 << (nbits - l)]                # (P, nodes_l)
            cnt = jnp.concatenate(
                [sc[:, 1:], jnp.full((num_chunks, 1), m, _I32)],
                axis=1) - sc                                 # per-chunk len
            across = exclusive_sum(cnt, axis=0)              # (P, nodes_l)
            gs = node_starts[l][:nodes_l]
            # output runs in (node-major, chunk-minor) order; run (v, c)
            # starts at gs[v] + across[c, v] — globally non-decreasing
            run_start = (gs[:, None] + across.T).reshape(-1)
            rid = segment_ids_from_starts(run_start, n)
            src_base = sc.T.reshape(-1)                      # rid -> sc[c,v]
            src = ((rid % num_chunks) * m + src_base[rid]
                   + (p_out - run_start[rid]))
            merged = bits_all[:, l, :].reshape(-1)[src]
            level_words.append(_pack_level(merged))
        return _finalize_fused(level_words, node_starts, n, nbits,
                               sample_rate)

    def chunk_levels(chunk):
        """Per-chunk levelwise build; returns (nbits, m) bits and node ids."""
        order = chunk
        bits_out, nids_out = [], []
        for l in range(nbits):
            bit = ((order >> _U32(nbits - 1 - l)) & _U32(1)).astype(_I32)
            nid = (order >> _U32(nbits - l)).astype(_I32) if l else \
                jnp.zeros((m,), _I32)
            bits_out.append(bit)
            nids_out.append(nid)
            if l < nbits - 1:
                dest = _segmented_partition_dest(nid, bit, l + 1)
                order = order[_invert_permutation(dest)]
        return jnp.stack(bits_out), jnp.stack(nids_out)

    bits_all, nids_all = jax.vmap(chunk_levels)(chunks)
    # bits_all, nids_all: (P, nbits, m) → per level merge
    level_words = []
    for l in range(nbits):
        bits_l = bits_all[:, l, :]                        # (P, m)
        nid_l = nids_all[:, l, :]                         # (P, m)
        nodes_l = 1 << l
        flat = (jnp.arange(num_chunks, dtype=_I32)[:, None] * nodes_l
                + nid_l)                                  # (P, m)
        cnt = (jnp.zeros((num_chunks * nodes_l,), _I32)
               .at[flat.reshape(-1)].add(1).reshape(num_chunks, nodes_l))
        across = exclusive_sum(cnt, axis=0)               # (P, nodes_l)
        chunk_node_start = exclusive_sum(cnt, axis=1)     # within-chunk
        global_start = node_starts[l, ::1][: nodes_l] if nodes_l == size \
            else node_starts[l, :nodes_l]
        pos_in_chunk = jnp.arange(m, dtype=_I32)[None, :]
        q = pos_in_chunk - jnp.take_along_axis(chunk_node_start, nid_l, axis=1)
        dest = (global_start[nid_l]
                + jnp.take_along_axis(across, nid_l, axis=1) + q)
        merged = (jnp.zeros((n,), _I32)
                  .at[dest.reshape(-1)].set(bits_l.reshape(-1),
                                            unique_indices=True))
        level_words.append(_pack_level(merged))
    return _finalize(level_words, node_starts, n, nbits, sample_rate)


# --------------------------------------------------------------------------
# Queries (levelwise layout)
# --------------------------------------------------------------------------

def wt_access(wt: WaveletTree, i: jax.Array) -> jax.Array:
    i = jnp.asarray(i, _I32)
    c = jnp.zeros_like(i)
    p = i
    v = jnp.zeros_like(i)
    for l in range(wt.nbits):
        bv = wt.level(l)
        s = wt.node_starts[l][v]
        bit = access_bit(bv.rank, p)
        rb = jnp.where(bit == 0,
                       rank0(bv.rank, p) - rank0(bv.rank, s),
                       rank1(bv.rank, p) - rank1(bv.rank, s))
        v = (v << 1) | bit
        c = (c << 1) | bit
        if l < wt.nbits - 1:
            p = wt.node_starts[l + 1][v] + rb
        else:
            p = wt.node_starts[wt.nbits][v] + rb
    return c


def wt_rank(wt: WaveletTree, c: jax.Array, i: jax.Array) -> jax.Array:
    """# of occurrences of c in [0, i)."""
    c = jnp.asarray(c, _I32)
    i = jnp.asarray(i, _I32)
    p = i
    v = jnp.zeros_like(i)
    for l in range(wt.nbits):
        bv = wt.level(l)
        s = wt.node_starts[l][v]
        p = jnp.minimum(p, _next_start(wt, l, v))
        bit = (c >> (wt.nbits - 1 - l)) & 1
        rb = jnp.where(bit == 0,
                       rank0(bv.rank, p) - rank0(bv.rank, s),
                       rank1(bv.rank, p) - rank1(bv.rank, s))
        v = (v << 1) | bit
        p = (wt.node_starts[l + 1][v] if l < wt.nbits - 1
             else wt.node_starts[wt.nbits][v]) + rb
    return p - wt.node_starts[wt.nbits][c]


def _next_start(wt: WaveletTree, l: int, v: jax.Array) -> jax.Array:
    """End offset of node v at level l (start of the next node, or n)."""
    nodes_l = 1 << l
    nxt = v + 1
    return jnp.where(nxt >= nodes_l, wt.n, wt.node_starts[l][jnp.minimum(nxt, nodes_l - 1)])


def wt_select(wt: WaveletTree, c: jax.Array, k: jax.Array) -> jax.Array:
    """Position of the k-th (0-based) occurrence of c."""
    c = jnp.asarray(c, _I32)
    k = jnp.asarray(k, _I32)
    pos = k
    for l in range(wt.nbits - 1, -1, -1):
        bv = wt.level(l)
        v = c >> (wt.nbits - l)
        s = wt.node_starts[l][v]
        bit = (c >> (wt.nbits - 1 - l)) & 1
        abs_rank = jnp.where(bit == 0,
                             rank0(bv.rank, s) + pos,
                             rank1(bv.rank, s) + pos)
        p_abs = jnp.where(bit == 0,
                          select0(bv.rank, bv.sel0, abs_rank),
                          select1(bv.rank, bv.sel1, abs_rank))
        pos = p_abs - s
    return pos
