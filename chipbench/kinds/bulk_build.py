"""Closed loop of bulk builds: token chunks turned into servable stores.

Traffic keys: ``shards_per_chip`` (a chunk is that many shards per chip
of the cell), ``chunks`` (distinct chunks made from the seed at set-up
and cycled through). Configuration keys: ``sigma``, ``shard_bits``,
``tau``, ``big_step``, ``select_sample_rate`` and the Zipf stream's
``zipf_exponent``, ``doc_len``, ``eos_id``.

Each build goes through ``build_sharded_analytics`` and is blocked until
ready; the window counts only builds started inside it and run to the
end. The comparison takes every level bitmap, zero count, rank directory
and select directory of every build against ``reference.wavelet_matrix``
on the chunk's raw tokens.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from chipbench import data, reference

LEAVES = ("words", "superblock", "block", "sel1", "sel0", "zeros")


def store_leaves(store) -> dict:
    """The named leaves of a ``ShardedAnalytics``' stacked wavelet
    matrices, each with leading (shards, levels) axes."""
    wm = store.shards
    bv = wm.bitvectors
    return {"words": bv.rank.words, "superblock": bv.rank.superblock,
            "block": bv.rank.block, "sel1": bv.sel1.sample,
            "sel0": bv.sel0.sample, "zeros": wm.zeros}


def program_build(cfg: dict):
    from repro.analytics.engine import build_sharded_analytics

    def build(tokens):
        return build_sharded_analytics(
            tokens, cfg["sigma"], shard_bits=cfg["shard_bits"],
            tau=cfg["tau"], big_step=cfg["big_step"],
            sample_rate=cfg["select_sample_rate"])
    return build


def nbits_of(sigma: int) -> int:
    return max(1, int(np.ceil(np.log2(max(2, sigma)))))


@dataclass
class State:
    cfg: dict
    chunks: list
    chunk_tokens: int
    build: object
    bits_per_token: float


def setup(ctx) -> State:
    import jax
    cfg, tr = ctx.config, ctx.traffic
    chunk = tr["shards_per_chip"] * ctx.chips << cfg["shard_bits"]
    t0 = time.perf_counter()
    toks = data.zipf_tokens(ctx.seed, 1, chunk * tr["chunks"], cfg["sigma"],
                            cfg["zipf_exponent"], cfg["doc_len"],
                            cfg["eos_id"])
    chunks = [toks[i * chunk:(i + 1) * chunk] for i in range(tr["chunks"])]
    print(f"setup data: {tr['chunks']} chunks of {chunk} tokens in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    build = ctx.hooks.get("build") or program_build(cfg)
    mark, t0 = ctx.meter.mark(), time.perf_counter()
    store = jax.block_until_ready(build(chunks[0]))
    bits = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(store))
    print(f"setup warm build: {time.perf_counter() - t0:.3f} s "
          f"{ctx.meter.since(mark)}", flush=True)
    return State(cfg, chunks, chunk, build, 8.0 * bits / chunk)


def window(st: State, seconds: float, ctx) -> dict:
    import jax
    built = []
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        with ctx.span("build"):
            store = jax.block_until_ready(st.build(st.chunks[i % len(
                st.chunks)]))
        built.append((i % len(st.chunks), store))
        i += 1
    elapsed = time.perf_counter() - t0
    return {"builds": len(built), "tokens": len(built) * st.chunk_tokens,
            "elapsed_s": elapsed, "_built": built}


def metrics(st: State, res: dict) -> dict:
    return {"build_tokens_per_s": res["tokens"] / res["elapsed_s"],
            "bits_per_token": st.bits_per_token}


def check(st: State, res: dict, ctx):
    """Every leaf of every build in the window against the reference of
    its chunk: the count of elements that differ (exact, limit 0)."""
    built, host = res.pop("_built"), []
    leaves = ctx.hooks.get("leaves") or store_leaves
    while built:                          # free each store once read
        c, store = built.pop(0)
        host.append((c, {k: np.asarray(v) for k, v in leaves(store).items()}))
        del store
    cfg = st.cfg
    n = 1 << cfg["shard_bits"]
    wrong = shape_errors = 0
    refs = {}
    for c, got in host:
        if c not in refs:
            shards = st.chunks[c].reshape(-1, n)
            refs[c] = reference.wavelet_matrix(
                shards, nbits_of(cfg["sigma"]), cfg["select_sample_rate"])
        for k in LEAVES:
            want = refs[c][k]
            if got[k].shape != want.shape:
                shape_errors += 1
                continue
            wrong += int(np.count_nonzero(got[k].astype(np.int64)
                                          != want.astype(np.int64)))
    checks = {"wrong_leaf_elements": {"value": wrong, "limit": 0},
              "leaf_shape_errors": {"value": shape_errors, "limit": 0}}
    return res["builds"], 0, checks
