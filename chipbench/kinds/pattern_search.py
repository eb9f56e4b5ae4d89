"""Closed loop of FM-index count batches: one client, one batch in flight.

The client takes its next batch of ``batch`` patterns when the previous
answer is back. Patterns come from a pool of ``pool`` made at set-up:
``substring_share`` of them substrings of the text, the rest uniform over
the alphabet (mostly misses), lengths uniform in [``min_len``,
``max_len``], drawn with Zipf(``popularity_exponent``) popularity.
``seam_share`` of the pool are substrings cut across a shard seam, so
that every window asks for matches that only the seam windows find. The
lengths stay within ``seam_overlap + 1``, where the program's count is
exact across shard seams.

Configuration keys: the text stream (``n_tokens``, ``sigma``,
``zipf_exponent``, ``doc_len``, ``eos_id``, ``data_seed``) and the index's
build settings (``shard_bits``, ``sa_sample_rate``, ``seam_overlap``).
The index is the deployment's text, made from ``data_seed``; the run's
seed draws the patterns. It is built once per checkout and program and
restored after that from the benchmark's own dump of its leaves.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from chipbench import cache, data, reference


def chipbench_fm_count(index, patterns, lengths):
    """The timed program: ``ShardedTextIndex.count``, under a stable name
    that the trace reduction finds."""
    return index.count(patterns, lengths)


def corpus(cfg: dict) -> np.ndarray:
    return data.zipf_tokens(cfg["data_seed"], 0, cfg["n_tokens"],
                            cfg["sigma"], cfg["zipf_exponent"],
                            cfg["doc_len"], cfg["eos_id"])


def cached_index(ctx, text: np.ndarray):
    from repro.index import build_sharded_index
    cfg = ctx.config
    where = ctx.cache_dir / cache.key(cfg, "fm") / "index.pkl"
    mark, t0 = ctx.meter.mark(), time.perf_counter()
    if where.exists():
        with ctx.span("restore"):
            index = cache.load_leaves(where)
        how = "restore"
    else:
        with ctx.span("build"):
            index = build_sharded_index(
                text, cfg["sigma"], shard_bits=cfg["shard_bits"],
                sample_rate=cfg["sa_sample_rate"],
                seam_overlap=cfg["seam_overlap"])
        cache.save_leaves(index, where)
        how = "build+save"
    import jax
    jax.block_until_ready(index)
    print(f"setup index {how}: {time.perf_counter() - t0:.3f} s "
          f"{ctx.meter.since(mark)}", flush=True)
    return index


@dataclass
class State:
    cfg: dict
    tr: dict
    text: np.ndarray
    count: object         # (patterns, lengths) -> (batch,) counts
    pats: np.ndarray      # (pool, max_len) host patterns
    lens: np.ndarray
    order: np.ndarray     # (batches, batch) pool indices, cycled
    dev: list             # per batch: (patterns, lengths) on the device
    bits_per_token: float
    seed: int


def setup(ctx) -> State:
    import jax
    import jax.numpy as jnp
    cfg, tr = ctx.config, ctx.traffic
    if tr["max_len"] > cfg["seam_overlap"] + 1:
        raise ValueError("patterns longer than seam_overlap + 1 are not "
                         "counted exactly across seams")
    t0 = time.perf_counter()
    text = corpus(cfg)
    g = data.rng(ctx.seed, 4)
    pats, lens = data.pattern_pool(g, text, cfg["sigma"], tr["pool"],
                                   tr["substring_share"], tr["min_len"],
                                   tr["max_len"], tr["seam_share"],
                                   1 << cfg["shard_bits"])
    order = data.zipf_draws(g, tr["pool"], tr["batches"] * tr["batch"],
                            tr["popularity_exponent"]).reshape(
        tr["batches"], tr["batch"])
    dev = [(jnp.asarray(pats[o]), jnp.asarray(lens[o])) for o in order]
    print(f"setup data: {len(text)} symbols, {tr['pool']} patterns, "
          f"{tr['batches']} batches in {time.perf_counter() - t0:.3f} s",
          flush=True)
    if "count" in ctx.hooks:
        count, bits = ctx.hooks["count"](ctx, text), 0.0
    else:
        index = cached_index(ctx, text)
        bits = 8.0 * sum(l.size * l.dtype.itemsize
                         for l in jax.tree.leaves(index)) / index.n
        fn = jax.jit(chipbench_fm_count)

        def count(p, ln):
            return fn(index, p, ln)
        mark, t0 = ctx.meter.mark(), time.perf_counter()
        jax.block_until_ready(count(*dev[0]))
        print(f"setup warm: {time.perf_counter() - t0:.3f} s "
              f"{ctx.meter.since(mark)}", flush=True)
    return State(cfg, tr, text, count, pats, lens, order, dev, bits,
                 ctx.seed)


def window(st: State, seconds: float, ctx) -> dict:
    import jax
    outs = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with ctx.span("count"):
            outs.append(jax.block_until_ready(
                st.count(*st.dev[len(outs) % len(st.dev)])))
    elapsed = time.perf_counter() - t0
    return {"batches": len(outs), "patterns": len(outs) * st.tr["batch"],
            "elapsed_s": elapsed, "_outs": outs}


def metrics(st: State, res: dict) -> dict:
    return {"search_patterns_per_s": res["patterns"] / res["elapsed_s"],
            "bits_per_token": st.bits_per_token}


def check(st: State, res: dict, ctx):
    """Every answer must agree with every other answer to the same pool
    pattern, and the raw text must agree with a seeded sample of the
    served pool patterns, the longest and every one that occurs across a
    shard seam always among them."""
    outs = [np.asarray(o) for o in res.pop("_outs")]
    st.count = st.dev = None                    # free the program's state
    got = np.stack(outs) if outs else np.zeros((0, st.tr["batch"]), int)
    idx = st.order[np.arange(len(outs)) % len(st.order)]
    first = {}
    inconsistent = 0
    for i, v in zip(idx.ravel().tolist(), got.ravel().tolist()):
        inconsistent += int(first.setdefault(i, v) != v)
    served = np.asarray(sorted(first))
    g = data.rng(st.seed, 5)
    pick = set(g.choice(served, min(len(served), st.tr["verify_sample"]),
                        replace=False).tolist()) if len(served) else set()
    if len(served):
        pick.add(int(served[np.argmax(st.lens[served])]))
        seam = reference.crosses_seam(st.text, st.pats[served],
                                      st.lens[served],
                                      1 << st.cfg["shard_bits"])
        pick.update(served[seam].tolist())
    print(f"check: {len(pick)} of {len(served)} served patterns against the "
          f"raw text", flush=True)
    wrong = sum(int(first[i] != reference.pattern_count(
        st.text, st.pats[i, :st.lens[i]])) for i in sorted(pick))
    checks = {"wrong_counts": {"value": wrong, "limit": 0},
              "inconsistent_counts": {"value": inconsistent, "limit": 0}}
    return int(res["patterns"]), 0, checks
