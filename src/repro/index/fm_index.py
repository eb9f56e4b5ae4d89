"""FM-index: backward search over a wavelet-matrix BWT (count + locate).

This is *the* workload the paper's structures exist for: every step of
backward search is two LF mappings on the BWT's wavelet matrix, so a
batch of B patterns of length L issues 2·B·L of them — all independent,
all vmapped. An LF mapping is one descent of its position down the
levels (one ``rank1`` each) plus a gather from a per-shard base table
made from ``C``. The index is a frozen-dataclass pytree (arrays are
leaves, sizes static), so it crosses ``jax.jit`` boundaries and vmaps
like any other operand.

Structure (Ferragina–Manzini, wavelet-matrix occ as in Claude & Navarro):

* ``wm``       — WaveletMatrix over the BWT of ``T·$`` (working alphabet
                 [0, σ]; raw symbol c stored as c+1, terminator 0).
* ``C``        — boundary table, C[c] = # of BWT symbols < c.
* ``mark``/``sa_sample`` — Clark-style sampled suffix array for ``locate``:
                 rows j with sa[j] ≡ 0 (mod sample_rate) are marked in a
                 rank bitvector and their sa values stored compacted in row
                 order; a locate walks LF at most sample_rate−1 steps to a
                 marked row (each step = 1 access descent of the wavelet
                 matrix), then reads the sample. Space for samples is
                 O(m/sample_rate) words — the index stays succinct.

TPU adaptations: backward search runs as a ``lax.fori_loop`` over pattern
positions with padded fixed-length patterns (padding masked by a length
vector, so ragged batches are one jitted call); the LF walk in ``locate``
is a fixed ``sample_rate``-trip loop with a done-mask instead of a
data-dependent while, keeping the schedule static for the compiler.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.rank_select import (BinaryRank, access_bit,
                                    build_binary_rank, rank1)
from repro.core import bitops
from repro.core.wavelet_matrix import (WaveletMatrix, build_wavelet_matrix,
                                       wm_access_descend, wm_block_starts,
                                       wm_descend)

from .bwt import SENTINEL_SHIFT, bwt_encode

_I32 = jnp.int32


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class FMIndex:
    """Succinct full-text index over one text shard. All-array pytree."""
    wm: WaveletMatrix       # BWT wavelet matrix, m = n+1 positions
    C: jax.Array            # (sigma+2,) int32 symbol boundaries
    mark: BinaryRank        # m bits: row j marked iff sa[j] % sample_rate == 0
    sa_sample: jax.Array    # (ceil(m/sample_rate),) int32, compacted row order
    n: int = field(metadata=dict(static=True))        # text length (no $)
    sigma: int = field(metadata=dict(static=True))    # raw alphabet size
    sample_rate: int = field(metadata=dict(static=True))

    @property
    def m(self) -> int:
        return self.n + 1

    # ------------------------------------------------------------------
    # queries (thin wrappers over the module functions)
    # ------------------------------------------------------------------
    def count(self, patterns: jax.Array, lengths: jax.Array) -> jax.Array:
        return fm_count(self, patterns, lengths)

    def locate(self, pattern: jax.Array, length: jax.Array,
               max_hits: int = 16) -> jax.Array:
        return fm_locate(self, pattern, length, max_hits)

    def bits_per_symbol(self) -> float:
        total = sum(l.size * l.dtype.itemsize * 8
                    for l in jax.tree.leaves(self))
        return total / max(1, self.n)


def build_fm_index(seq, sigma: int, *, sample_rate: int = 32,
                   tau: int = 8, big_step: str = "compose",
                   bv_sample_rate: int = 512,
                   backend: str = "counting") -> FMIndex:
    """Build the index: parallel SA (prefix doubling) → BWT gather → paper
    wavelet-matrix construction (Theorem 4.5) → sampled-SA directories.

    Fully trace-safe (no host syncs on data values), so whole-shard builds
    can run under ``vmap``/``pmap`` — see ``data.shard_build``. The
    out-of-alphabet validation only fires on concrete inputs.
    """
    seq = jnp.asarray(seq)
    concrete = not isinstance(seq, jax.core.Tracer)
    if concrete and seq.size and (int(jnp.min(seq)) < 0
                                  or int(jnp.max(seq)) >= sigma):
        # a symbol ≥ σ would be silently dropped from C and truncated by
        # the wavelet matrix — corrupt counts with no error downstream
        raise ValueError(f"symbols outside [0, {sigma})")
    bwt, sa, C = bwt_encode(seq, sigma, backend=backend)
    m = int(bwt.shape[0])
    sigma_work = sigma + SENTINEL_SHIFT
    wm = build_wavelet_matrix(bwt, sigma_work, tau=tau, big_step=big_step,
                              sample_rate=bv_sample_rate)

    marked = (sa % sample_rate) == 0
    # sa is a permutation of [0, m): exactly ceil(m/sample_rate) multiples,
    # compacted in row order by a scatter on the marked-prefix count
    num_samples = (m + sample_rate - 1) // sample_rate
    cnt = jnp.cumsum(marked.astype(_I32)) - 1
    sample_vals = jnp.zeros((num_samples,), _I32).at[
        jnp.where(marked, cnt, num_samples)].set(
            sa.astype(_I32), mode="drop")
    words = bitops.pack_bits(bitops.pad_bits(marked.astype(jnp.uint8)))
    mark = build_binary_rank(words, m)
    return FMIndex(wm=wm, C=C, mark=mark, sa_sample=sample_vals,
                   n=int(seq.shape[0]), sigma=sigma,
                   sample_rate=sample_rate)


# ----------------------------------------------------------------------
# backward search
# ----------------------------------------------------------------------

def _lf_base(fm: FMIndex) -> jax.Array:
    """(σ+2,) table with LF(c, i) = base[c] + wm_descend(wm, c, i).

    LF(c, i) = C[c] + rank(c, i), and the descent of i along c ends at
    start[c] + rank(c, i), where start[c] is the first row of c's block at
    the last level. So base = C − start, with start from C's differences
    (the out-of-alphabet code σ+1 never occurs: count 0)."""
    counts = jnp.diff(fm.C, append=fm.C[-1:])
    return fm.C - wm_block_starts(counts, fm.wm.nbits)


def _backward_range(fm: FMIndex, pattern: jax.Array,
                    length: jax.Array):
    """(lo, hi) of the SA range matching one padded pattern.

    ``pattern``: (L,) raw symbols in [0, σ), padding anywhere at t ≥ length.
    Out-of-alphabet "symbols" (e.g. σ used as padding) never match: their
    shifted id clips to the C-table edge and the range empties.
    """
    pattern = jnp.asarray(pattern, _I32)
    length = jnp.asarray(length, _I32)
    L = pattern.shape[0]
    m = jnp.asarray(fm.m, _I32)
    # one descent per range end, nbits rank1 each
    obs.counter("index.backward_step",
                rank1_per_step=2 * fm.wm.nbits).inc()
    lf_base = _lf_base(fm)

    def body(t, state):
        lo, hi = state
        i = L - 1 - t                     # right-to-left
        c = jnp.clip(pattern[i] + SENTINEL_SHIFT, 0, fm.sigma + 1)
        in_alpha = (pattern[i] >= 0) & (pattern[i] < fm.sigma)
        active = i < length
        base = lf_base[c]
        hi2 = base + wm_descend(fm.wm, c, hi)
        # an out-of-alphabet symbol (e.g. shard padding) empties the range
        lo2 = jnp.where(in_alpha, base + wm_descend(fm.wm, c, lo), hi2)
        lo = jnp.where(active, lo2, lo)
        hi = jnp.where(active, hi2, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, L, body, (jnp.zeros((), _I32), m))
    return lo, hi


def fm_count(fm: FMIndex, patterns: jax.Array,
             lengths: jax.Array) -> jax.Array:
    """# of occurrences of each pattern in the text. Vmapped over batch.

    ``patterns``: (B, L) int32, padded; ``lengths``: (B,) true lengths.
    A zero-length pattern counts every position (m matches of the empty
    string, including before the terminator) — callers that want n+1 or 0
    should mask.
    """
    patterns = jnp.atleast_2d(jnp.asarray(patterns, _I32))
    lengths = jnp.atleast_1d(jnp.asarray(lengths, _I32))

    def one(p, l):
        lo, hi = _backward_range(fm, p, l)
        return hi - lo

    return jax.vmap(one)(patterns, lengths)


# ----------------------------------------------------------------------
# locate (sampled-SA LF walk)
# ----------------------------------------------------------------------

def _lf_step(fm: FMIndex, lf_base: jax.Array, j: jax.Array) -> jax.Array:
    """LF(j): the row whose suffix starts one text position earlier.
    Reading BWT[j] descends j along its own symbol, so the descent's end
    is the rank part of LF: one descent, no second rank."""
    c, end = wm_access_descend(fm.wm, j)
    return lf_base[c] + end


def _locate_row(fm: FMIndex, lf_base: jax.Array,
                j: jax.Array) -> jax.Array:
    """Text position of SA row j: walk LF to the nearest marked row."""
    j = jnp.asarray(j, _I32)

    def body(_, state):
        j, steps, done = state
        done2 = done | (access_bit(fm.mark, j) > 0)
        j2 = jnp.where(done2, j, _lf_step(fm, lf_base, j))
        steps2 = jnp.where(done2, steps, steps + 1)
        return j2, steps2, done2

    j, steps, _ = jax.lax.fori_loop(
        0, fm.sample_rate, body, (j, jnp.zeros((), _I32),
                                  jnp.zeros((), bool)))
    sample = fm.sa_sample[rank1(fm.mark, j)]
    return (sample + steps) % jnp.asarray(fm.m, _I32)


def fm_locate(fm: FMIndex, pattern: jax.Array, length: jax.Array,
              max_hits: int = 16) -> jax.Array:
    """Text positions of up to ``max_hits`` matches of one pattern.

    Returns (max_hits,) int32, sorted ascending, padded with -1 past the
    true match count. Each hit is an independent LF walk → vmapped.
    """
    lo, hi = _backward_range(fm, jnp.asarray(pattern, _I32),
                             jnp.asarray(length, _I32))
    ks = jnp.arange(max_hits, dtype=_I32)
    rows = jnp.minimum(lo + ks, jnp.asarray(fm.m - 1, _I32))
    lf_base = _lf_base(fm)
    pos = jax.vmap(lambda r: _locate_row(fm, lf_base, r))(rows)
    valid = ks < (hi - lo)
    out = jnp.sort(jnp.where(valid, pos, jnp.asarray(fm.m, _I32)))
    return jnp.where(out >= fm.m, jnp.asarray(-1, _I32), out)
