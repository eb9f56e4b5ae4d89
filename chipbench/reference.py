"""Plain numpy references of what the program computes, and the controls.

Nothing here imports the program or reads anything it made: each
function works from the raw token stream the benchmark generated.

* ``wavelet_matrix`` — the stacked wavelet-matrix leaves of a batch of
  shards (level bitmaps, zero counts, two-level rank directory, sampled
  select hints), by their textbook definitions.
* ``range_count`` / ``range_quantile`` — answers on the raw stream.
* ``pattern_count`` — substring occurrences by candidate narrowing.

Each control breaks one guarantee that the configurations state, the way
a tempting shortcut would; the benchmark's comparison must fail it.
"""
from __future__ import annotations

import numpy as np

WORD_BITS = 32
SUPERBLOCK_WORDS = 32        # 1024-bit superblocks
BLOCK_WORDS = 4              # 128-bit blocks


def _pack(bits: np.ndarray) -> np.ndarray:
    """(S, n) 0/1 → (S, ceil(n/32)) uint32, bit i at word i//32, LSB i%32."""
    S, n = bits.shape
    pad = (-n) % WORD_BITS
    if pad:
        bits = np.concatenate([bits, np.zeros((S, pad), bits.dtype)], 1)
    return np.ascontiguousarray(
        np.packbits(bits.astype(np.uint8), axis=1, bitorder="little")
    ).view("<u4")


def _popcount(words: np.ndarray) -> np.ndarray:
    return np.unpackbits(words.view(np.uint8), axis=-1).reshape(
        words.shape + (32,)).sum(-1, dtype=np.int64)


def rank_directory(words: np.ndarray):
    """superblock[k] = ones before word 32k (uint32); block[b] = ones in
    b's superblock before word 4b (uint16). ``words``: (S, W)."""
    pop = _popcount(words)
    prefix = np.concatenate([np.zeros((len(words), 1), np.int64),
                             np.cumsum(pop, 1)[:, :-1]], 1)
    superblock = prefix[:, ::SUPERBLOCK_WORDS]
    blk = prefix[:, ::BLOCK_WORDS]
    sb_of = np.arange(blk.shape[1]) // (SUPERBLOCK_WORDS // BLOCK_WORDS)
    return (superblock.astype(np.uint32),
            (blk - superblock[:, sb_of]).astype(np.uint16))


def select_samples(words: np.ndarray, n: int, rate: int, zeros: bool):
    """sample[j] = the 128-bit block holding the (j·rate)-th target bit
    (1s, or 0s among the n valid bits), clipped to the last block, for
    j < n // rate + 2."""
    S, W = words.shape
    nblk = -(-W // BLOCK_WORDS)
    padded = np.zeros((S, nblk * BLOCK_WORDS), np.uint32)
    padded[:, :W] = words
    ones = _popcount(padded).reshape(S, nblk, BLOCK_WORDS).sum(-1)
    if zeros:
        valid = np.clip(n - np.arange(nblk) * BLOCK_WORDS * WORD_BITS, 0,
                        BLOCK_WORDS * WORD_BITS)
        ones = valid[None, :] - ones
    cum = np.concatenate([np.zeros((S, 1), np.int64), np.cumsum(ones, 1)], 1)
    targets = np.arange(n // rate + 2) * rate
    return np.stack([np.clip(np.searchsorted(c, targets, side="right") - 1,
                             0, nblk - 1) for c in cum]).astype(np.int32)


def wavelet_matrix(shards: np.ndarray, nbits: int, sample_rate: int,
                   stable: bool = True) -> dict:
    """Leaves of the wavelet matrices of ``shards`` ((S, n) symbols), each
    with leading (S, nbits) axes. Level l holds bit nbits-1-l of the
    sequence as level l sees it; the next level sees it stably
    partitioned, zeros first. ``stable=False`` is the control: the ones
    come out in reverse order."""
    S, n = shards.shape
    seq = shards.astype(np.uint32)
    words, zeros = [], []
    for l in range(nbits):
        bit = ((seq >> np.uint32(nbits - 1 - l)) & np.uint32(1)).astype(bool)
        words.append(_pack(bit))
        zeros.append(n - bit.sum(1))
        if l < nbits - 1:
            nxt = np.empty_like(seq)
            for r in range(S):
                zs, ones = seq[r][~bit[r]], seq[r][bit[r]]
                nxt[r, :len(zs)] = zs
                nxt[r, len(zs):] = ones if stable else ones[::-1]
            seq = nxt
    flat = np.concatenate(words)              # level-major (L*S, W)
    sb, blk = rank_directory(flat)
    out = {"words": flat, "superblock": sb, "block": blk,
           "sel1": select_samples(flat, n, sample_rate, zeros=False),
           "sel0": select_samples(flat, n, sample_rate, zeros=True)}
    out = {k: v.reshape(nbits, S, -1).swapaxes(0, 1)
           for k, v in out.items()}
    out["zeros"] = np.stack(zeros, 1).astype(np.int32)
    return out


# ---- range analytics ------------------------------------------------------

def range_count(toks: np.ndarray, lo: int, hi: int, a: int, b: int) -> int:
    sl = toks[lo:hi]
    return int(np.count_nonzero((sl >= a) & (sl < b)))


def range_quantile(toks: np.ndarray, lo: int, hi: int, k: int,
                   sigma: int, shave: int = 0) -> int:
    """k-th smallest symbol of toks[lo:hi]. ``shave`` > 0 is the control:
    the answer of a descent cut ``shave`` levels short (low bits 0)."""
    cum = np.cumsum(np.bincount(toks[lo:hi], minlength=sigma))
    c = int(np.searchsorted(cum, k, side="right"))
    return (c >> shave) << shave


# ---- full-text search -----------------------------------------------------

def pattern_starts(text: np.ndarray, pat: np.ndarray) -> np.ndarray:
    """Every start of ``pat`` in ``text`` (candidates narrowed one symbol
    at a time)."""
    m = len(pat)
    cand = np.flatnonzero(text[:len(text) - m + 1] == pat[0])
    for j in range(1, m):
        cand = cand[text[cand + j] == pat[j]]
    return cand


def crosses_seam(text: np.ndarray, pats: np.ndarray, lens: np.ndarray,
                 size: int) -> np.ndarray:
    """(P,) bool: whether pattern i occurs across a boundary between
    shards of ``size`` symbols (starts before it, ends at or after it)."""
    m_max = int(lens.max())
    bounds = np.arange(size, len(text), size)
    cols = np.arange(-m_max + 1, m_max - 1)
    pos = np.clip(bounds[:, None] + cols[None, :], 0, len(text) - 1)
    win = text[pos]                                   # (seams, 2m-2)
    out = np.zeros(len(pats), bool)
    for m in np.unique(lens):
        sel = np.flatnonzero(lens == m)
        # starts b-m+1 .. b-1 cross the boundary b
        offs = np.arange(m_max - m, m_max - 1)
        slab = win[:, offs[:, None] + np.arange(m)[None, :]]
        hit = (slab[None] == pats[sel, None, None, :m]).all(-1)
        out[sel] = hit.any((1, 2))
    return out


def pattern_count(text: np.ndarray, pat: np.ndarray,
                  within_shard: int = 0) -> int:
    """Occurrences of ``pat``. ``within_shard`` > 0 is the control: it
    drops matches that cross a boundary between shards of that size."""
    starts = pattern_starts(text, pat)
    if within_shard:
        ends = starts + len(pat) - 1
        starts = starts[starts // within_shard == ends // within_shard]
    return int(len(starts))
