"""Stable parallel integer sorting — the paper's big-node primitive.

The paper's τ-chunked wavelet construction performs one *stable* integer sort
per big-node level, with keys of τ bits. It discusses two PRAM sorts:
an O(n loglog n)-work polylog-depth sort [BDH+91, RR89] and a work-efficient
O(n/ε)-work O(n^ε/ε)-depth sort [Vishkin]. Neither has a TPU analogue, so we
provide the two TPU-native realizations (both stable):

* ``backend="counting"`` — LSD counting sort built from histograms + prefix
  sums: O(n + 2^pass_bits) work per pass and O(log n) scan depth. This is the
  paper-faithful backend — "stable integer sort via prefix sums" — and
  vectorizes over the whole array. For wide digits the stable rank runs
  blocked (per-block histogram → cross-block scan → within-block rank, the
  same block-local-count-then-scan structure as the paper's domain-
  decomposition merge) through an XLA realization that vectorizes groups
  of blocks under a bounded one-hot working set. The Pallas
  ``kernels.radix_rank`` form is opt-in (``use_kernel=True``).
* ``backend="xla"`` — ``jax.lax.sort`` (stable), the vendor-shipped sort.

Both are benchmarked against each other in ``benchmarks/run.py``. The
counting backend is the paper's Theorem 4.5 big-node sort and also drives
every suffix-array doubling round (``repro.index.suffix_array``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .scan import exclusive_sum

# One-hot rank computation is fully vectorized when the bucket count is at
# most this; beyond it, the blocked path bounds the one-hot working set.
_VECTORIZED_BUCKET_LIMIT = 32
_BLOCK = 512
# The blocked path vectorizes groups of blocks as long as the group's
# one-hot stays under this many int32 elements; larger problems fall back
# to lax.map over the groups.
_ONEHOT_BUDGET = 1 << 25


@functools.partial(jax.jit, static_argnames=("num_buckets",))
def _counting_rank_vectorized(digits: jax.Array, num_buckets: int) -> jax.Array:
    """Stable destination of each element when sorting by ``digits``.

    dest[i] = (# elements with smaller digit) + (# j<i with digit==digits[i]).
    The first term is an exclusive sum over the histogram; the second an
    exclusive column-wise sum over the one-hot matrix. O(n·B) space — used
    for small bucket counts only.
    """
    digits = digits.astype(jnp.int32)
    hist = jnp.zeros((num_buckets,), jnp.int32).at[digits].add(1, mode="drop")
    bucket_base = exclusive_sum(hist)
    onehot = jax.nn.one_hot(digits, num_buckets, dtype=jnp.int32)
    within = exclusive_sum(onehot, axis=0)
    rank_within = jnp.take_along_axis(within, digits[:, None], axis=1)[:, 0]
    return bucket_base[digits] + rank_within


@functools.partial(jax.jit, static_argnames=("num_buckets", "block"))
def _blocked_rank_parts(digits: jax.Array, num_buckets: int,
                        block: int = _BLOCK):
    """Memory-lean within-bucket stable rank + bucket totals.

    Returns ``(within, totals)``: ``within[i]`` = # of j < i with
    digits[j] == digits[i]; ``totals`` the (num_buckets + 1,) histogram
    (sentinel bucket last) — so callers that also need bucket bases don't
    histogram the array a second time.

    Per-block histogram → cross-block exclusive scan (each block's
    per-bucket offset) → within-block equal-before counts. The within-block
    one-hots are vectorized over groups of blocks sized to the
    ``_ONEHOT_BUDGET`` working set, with ``lax.map`` over the groups only
    when the problem exceeds one group — so moderate inputs (e.g. every
    suffix-array doubling round) run as a single fused XLA op. Padding
    elements go to a sentinel bucket after all real buckets.
    """
    n = digits.shape[0]
    B1 = num_buckets + 1
    nb = -(-n // block)
    # blocks per group, clamped so small inputs never pad past their own
    # block count (a group larger than nb would inflate the one-hot)
    group = max(1, min(_ONEHOT_BUDGET // (block * B1), nb))
    ng = -(-nb // group)
    pad = ng * group * block - n
    sentinel = num_buckets
    d = jnp.concatenate([digits.astype(jnp.int32),
                         jnp.full((pad,), sentinel, jnp.int32)])
    nb = d.shape[0] // block
    db = d.reshape(nb, block)

    blk_ids = jnp.repeat(jnp.arange(nb, dtype=jnp.int32), block)
    flat = blk_ids * B1 + d
    block_hist = jnp.zeros((nb * B1,), jnp.int32).at[flat].add(1).reshape(nb, B1)
    across = exclusive_sum(block_hist, axis=0)                   # (nb, B1)

    def group_rank(dg):                                          # (g, block)
        onehot = jax.nn.one_hot(dg, B1, dtype=jnp.int32)
        within = exclusive_sum(onehot, axis=1)
        return jnp.take_along_axis(within, dg[..., None], axis=2)[..., 0]

    dgrp = db.reshape(ng, group, block)
    if ng == 1:
        rank_within = group_rank(dgrp[0])                        # (nb, block)
    else:
        rank_within = jax.lax.map(group_rank, dgrp).reshape(nb, block)
    out = jnp.take_along_axis(across, db, axis=1) + rank_within
    return out.reshape(-1)[:n], jnp.sum(block_hist, axis=0)


def counting_rank(digits: jax.Array, num_buckets: int,
                  use_kernel: bool = False) -> jax.Array:
    """Stable sort destinations (a permutation when there is no padding).

    dest[i] = (# elements with smaller digit) + (# j<i with equal digit) —
    the paper's "stable integer sort via prefix sums" (Section 2), used as
    the big-node sort of Theorem 4.5 and by every suffix-array doubling
    round. Routing: small bucket counts use the fully vectorized one-hot;
    large ones the blocked histogram→scan→within-block path, in XLA.
    ``use_kernel=True`` routes that path through the Pallas
    ``kernels.radix_rank`` kernel when the bucket count fits its VMEM bound;
    it is off by default because the kernel does not compile for a TPU
    v5e (its (1, B+1) histogram blocks are not (8,128)-aligned).
    """
    n = digits.shape[0]
    if num_buckets <= _VECTORIZED_BUCKET_LIMIT or n <= 4 * _BLOCK:
        return _counting_rank_vectorized(digits, num_buckets)
    if use_kernel:
        from repro.kernels import ops as _kops
        from repro.kernels import radix_rank as _rr
        if num_buckets <= _rr.MAX_BUCKETS:
            return _kops.radix_rank(digits, num_buckets)
    digits = digits.astype(jnp.int32)
    within, totals = _blocked_rank_parts(digits, num_buckets)
    bucket_base = exclusive_sum(totals)
    return bucket_base[digits] + within


def bucket_ranks(digits: jax.Array, num_buckets: int) -> jax.Array:
    """rank_within[i] = # of j < i with digits[j] == digits[i].

    The arrival-order rank inside each bucket — the same prefix-sum
    machinery as the stable counting sort, exposed for consumers like MoE
    token dispatch (DESIGN.md §3.2) where the bucket offset is implicit
    (capacity slots) rather than a sort destination. Small bucket counts
    use the fully vectorized one-hot; large ones route through the blocked
    path instead of materializing the O(n·B) matrix.
    """
    digits = digits.astype(jnp.int32)
    if num_buckets <= _VECTORIZED_BUCKET_LIMIT or digits.shape[0] <= 4 * _BLOCK:
        onehot = jax.nn.one_hot(digits, num_buckets, dtype=jnp.int32)
        within = exclusive_sum(onehot, axis=0)
        return jnp.take_along_axis(within, digits[:, None], axis=1)[:, 0]
    return _blocked_rank_parts(digits, num_buckets)[0]


def _invert_permutation(dest: jax.Array) -> jax.Array:
    """perm[k] = i such that dest[i] == k (dest must be a permutation)."""
    n = dest.shape[0]
    return jnp.zeros((n,), jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)


def sort_pass(keys: jax.Array,
              digits: jax.Array,
              num_buckets: int,
              values: Optional[Tuple[jax.Array, ...]] = None,
              backend: str = "counting"):
    """One stable sort pass by ``digits`` (each in [0, num_buckets)).

    Reorders ``keys`` (and optional tuple of ``values``) stably by digit.
    """
    if backend == "xla":
        operands = (digits.astype(jnp.int32), keys) + tuple(values or ())
        out = jax.lax.sort(operands, num_keys=1, is_stable=True)
        new_keys = out[1]
        new_values = tuple(out[2:]) if values is not None else None
        return new_keys, new_values
    if backend == "counting":
        dest = counting_rank(digits, num_buckets)
        perm = _invert_permutation(dest)
        new_keys = keys[perm]
        new_values = tuple(v[perm] for v in values) if values is not None else None
        return new_keys, new_values
    raise ValueError(f"unknown sort backend {backend!r}")


def sort_permutation(digits: jax.Array, num_buckets: int,
                     backend: str = "counting") -> jax.Array:
    """Gather permutation realizing the stable sort by ``digits``."""
    if backend == "xla":
        _, perm = jax.lax.sort(
            (digits.astype(jnp.int32),
             jnp.arange(digits.shape[0], dtype=jnp.int32)),
            num_keys=1, is_stable=True)
        return perm
    return _invert_permutation(counting_rank(digits, num_buckets))


def radix_sort_stable(keys: jax.Array,
                      key_bits: int,
                      values: Optional[Tuple[jax.Array, ...]] = None,
                      bits_per_pass: int = 8,
                      backend: str = "counting"):
    """LSD stable radix sort of integer ``keys`` with ``key_bits`` bits.

    ``bits_per_pass`` plays the role of the paper's τ: fewer, wider passes do
    less total data movement but need larger histograms — the same work/depth
    trade the paper optimizes with τ = √log n. Returns (keys, values).
    """
    kb = int(key_bits)
    shift = 0
    while shift < kb:
        width = min(bits_per_pass, kb - shift)
        digits = (keys.astype(jnp.uint32) >> jnp.uint32(shift)) & jnp.uint32((1 << width) - 1)
        keys, values = sort_pass(keys, digits, 1 << width, values, backend=backend)
        shift += width
    return keys, values
