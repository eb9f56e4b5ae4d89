#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU, with every answer checked.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four-chip shard build only

One process drives the chip through the library's own entry points, on
data made by ``make_corpus`` from ``--seed``:

1. device — print the devices; anything but a TPU exits non-zero at once.
2. vocabulary-sized store — ``build_sharded_analytics`` at n = 2^26 tokens,
   σ = 2^17, 2^16-token shards; count and quantile requests served through
   ``QueryFrontend(GenerationServer(engine))``. Every answer must be exact
   with full coverage, none shed; a sample per op is checked against numpy
   on the raw stream.
3. byte-alphabet text — ``build_sharded_index`` at n = 2^24, σ = 256:
   FM count and locate on 128 patterns drawn from the text; then
   ``build_sharded_analytics`` over the same stream: top-k and distinct on
   64-query batches. All checked against numpy.

``--chips 4`` runs only the multi-chip phase: the vocabulary-sized store
built through the pmap path (``jax.local_device_count() > 1``), built again
through the one-device loop, compared leaf by leaf, and the count/quantile
batch served from the pmap-built store.

Each phase prints one ``phase {...}`` JSON line: sizes, build seconds,
compile seconds and persistent-cache hits (from JAX's monitoring events),
first and steady call of each op (after ``block_until_ready``), peak device
bytes, and how many answers were verified. The last line is
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

QUERY_BATCH = 128          # the front-end's largest bucket
BATCHES_PER_OP = 3         # first call + two steady calls
VERIFY_PER_OP = 64
TOPK_K = 8
NUM_PATTERNS = 128
PATTERN_LEN = 8
LOCATE_HITS = 4            # per shard, as the index CLI asks


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, read from JAX's
    monitoring events (a cache hit is counted and its load time is what
    the compile event then measures)."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.compile_s, self.compiles, self.cache_hits

    def since(self, mark) -> dict:
        s, c, h = mark
        return {"compile_s": self.compile_s - s,
                "compiles": self.compiles - c,
                "cache_hits": self.cache_hits - h}


def peak_bytes() -> int:
    import jax
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def timed(fn, *args):
    """(out, seconds) of one call, synchronized with the device."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------
# numpy references on the raw stream
# --------------------------------------------------------------------------

def match_starts(toks: np.ndarray, pat: np.ndarray) -> np.ndarray:
    """Every start position of ``pat`` in ``toks`` (candidate filtering:
    the positions holding pat[0], narrowed one symbol at a time)."""
    m = len(pat)
    cand = np.flatnonzero(toks[:len(toks) - m + 1] == pat[0])
    for j in range(1, m):
        cand = cand[toks[cand + j] == pat[j]]
    return cand


def make_range_queries(rng, n: int, num: int):
    """Half narrow (≤ 4096 positions), half anywhere up to the whole
    corpus — the mix a data-audit user sends."""
    lo = rng.integers(0, n - 1, num)
    narrow = rng.random(num) < 0.5
    width = np.where(narrow, rng.integers(1, 4097, num),
                     rng.integers(1, n + 1, num))
    hi = np.minimum(lo + width, n)
    return lo.astype(np.int64), hi.astype(np.int64)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def serve_count_quantile(eng, toks: np.ndarray, sigma: int, seed: int,
                         meter: CompileMeter) -> dict:
    """Count and quantile requests through ``QueryFrontend``: every answer
    exact, full coverage, nothing shed; a sample checked against numpy."""
    from repro.ingest.serving import GenerationServer
    from repro.serving import BreakerConfig, FrontendConfig, QueryFrontend

    n = eng.n
    fe = QueryFrontend(
        GenerationServer(eng),
        config=FrontendConfig(
            buckets=(QUERY_BATCH,), capacity=8 * QUERY_BATCH,
            default_deadline_s=3600.0,
            # the real-clock probe timings of the front-end CLI
            breaker=BreakerConfig(probe_timeout_s=2.0, probe_interval_s=5.0,
                                  reset_after_s=2.0)))
    _, probe_first_s = timed(eng.probe_shard, 0)   # compile the probe
    out = {"probe_first_s": probe_first_s}
    rng = np.random.default_rng(seed)
    verified = answered = 0
    try:
        for op in ("count", "quantile"):
            mark = meter.mark()
            times, checks = [], []
            for _ in range(BATCHES_PER_OP):
                lo, hi = make_range_queries(rng, n, QUERY_BATCH)
                tickets = []
                for i in range(QUERY_BATCH):
                    if op == "count":
                        a = int(rng.integers(0, sigma))
                        b = int(rng.integers(a + 1, sigma + 1))
                        tickets.append((fe.submit(
                            "count", int(lo[i]), int(hi[i]), sym_lo=a,
                            sym_hi=b), (int(lo[i]), int(hi[i]), a, b)))
                    else:
                        k = int(rng.integers(0, hi[i] - lo[i]))
                        tickets.append((fe.submit(
                            "quantile", int(lo[i]), int(hi[i]), k=k),
                            (int(lo[i]), int(hi[i]), k)))
                t0 = time.perf_counter()
                served = fe.pump()
                times.append(time.perf_counter() - t0)
                if served != QUERY_BATCH:
                    raise AssertionError(
                        f"{op}: pump served {served} of {QUERY_BATCH}")
                for t, q in tickets:
                    a = t.result(timeout=0)
                    if a.mode != "exact" or a.coverage != 1.0 or a.degraded:
                        raise AssertionError(
                            f"{op} {q}: mode={a.mode} coverage="
                            f"{a.coverage} degraded={a.degraded}")
                    answered += 1
                    checks.append((q, a.value))
            for q, got in checks[:VERIFY_PER_OP]:
                sl = toks[q[0]:q[1]]
                if op == "count":
                    want = int(np.count_nonzero((sl >= q[2]) & (sl < q[3])))
                else:
                    want = int(np.partition(sl, q[2])[q[2]])
                if got != want:
                    raise AssertionError(f"{op} {q}: got {got}, want {want}")
                verified += 1
            out[op] = {"first_s": times[0],
                       "steady_s": float(np.median(times[1:])),
                       "batch": QUERY_BATCH, **meter.since(mark)}
    finally:
        fe.breakers.close_pool()
    stats = fe.stats()
    if stats["total_shed"] or stats["degraded_served"] or \
            stats["open_breakers"] or stats["queued"]:
        raise AssertionError(f"front-end stats {stats}")
    out.update(answered=answered, verified=verified,
               shed=stats["total_shed"], degraded=stats["degraded_served"])
    return out


def phase_vocab_store(log2n: int, sigma: int, seed: int,
                      meter: CompileMeter) -> dict:
    from repro.analytics.engine import build_sharded_analytics
    from repro.data import make_corpus

    n = 1 << log2n
    t0 = time.perf_counter()
    toks = np.asarray(make_corpus(n, sigma, seed=seed), np.int64)
    data_s = time.perf_counter() - t0
    log(f"vocab store: top-k and distinct skipped at sigma={sigma}: they "
        f"build a (queries, shards, sigma) histogram a 16 GB chip refuses "
        f"(ROADMAP Queue 1 item 4)")
    mark = meter.mark()
    eng, build_s = timed(lambda: build_sharded_analytics(
        toks, sigma, shard_bits=16))
    build = {"build_s": build_s, **meter.since(mark)}
    log(f"vocab store: built {eng.num_shards} shards in {build_s:.1f} s")
    served = serve_count_quantile(eng, toks, sigma, seed + 1, meter)
    return {"phase": "vocab_store", "n": n, "sigma": sigma,
            "shard_bits": 16, "num_shards": eng.num_shards,
            "bits_per_token": eng.bits_per_token(), "data_s": data_s,
            "build": build, **served, "peak_bytes_in_use": peak_bytes()}


def phase_byte_text(log2n: int, seed: int, meter: CompileMeter) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.analytics.engine import build_sharded_analytics
    from repro.data import make_corpus
    from repro.index import build_sharded_index, sample_patterns

    n, sigma = 1 << log2n, 256
    toks = np.asarray(make_corpus(n, sigma, seed=seed), np.int64)

    # ---- FM-index search ------------------------------------------------
    mark = meter.mark()
    idx, build_s = timed(lambda: build_sharded_index(
        toks, sigma, shard_bits=16))
    index_build = {"build_s": build_s, **meter.since(mark)}
    log(f"byte text: FM index of {idx.num_shards} shards built in "
        f"{build_s:.1f} s")
    pats, lens = sample_patterns(toks, NUM_PATTERNS, PATTERN_LEN, pad=sigma,
                                 seed=seed + 1)
    pj, lj = jnp.asarray(pats), jnp.asarray(lens)
    ops = {}
    results = {}
    for name, fn in (
            ("count", jax.jit(lambda ix, p, l: ix.count(p, l))),
            ("locate", jax.jit(lambda ix, p, l: ix.locate(p, l,
                                                          LOCATE_HITS)))):
        mark = meter.mark()
        out, first_s = timed(fn, idx, pj, lj)
        _, steady_s = timed(fn, idx, pj, lj)
        results[name] = np.asarray(out)
        ops[name] = {"first_s": first_s, "steady_s": steady_s,
                     "batch": NUM_PATTERNS, **meter.since(mark)}
    stitch_max = min(idx.seam_overlap + 1, idx.shard_size)
    if PATTERN_LEN > stitch_max:
        raise AssertionError("patterns longer than the seam-exact domain")
    size = idx.shard_size
    verified = 0
    for i in range(NUM_PATTERNS):
        m = int(lens[i])
        starts = match_starts(toks, pats[i, :m])
        if int(results["count"][i]) != len(starts):
            raise AssertionError(f"count pattern {i}: got "
                                 f"{results['count'][i]}, want {len(starts)}")
        hits = results["locate"][i]
        hits = hits[hits >= 0]
        inside = starts[starts // size == (starts + m - 1) // size]
        want = int(np.minimum(np.bincount(inside // size), LOCATE_HITS).sum())
        if (len(hits) != want or len(np.unique(hits)) != len(hits)
                or not np.isin(hits, inside).all()):
            raise AssertionError(f"locate pattern {i}: {len(hits)} hits, "
                                 f"want {want} distinct true matches")
        verified += 2
    search = {"index_build": index_build, **ops, "verified": verified}

    # ---- σ-wide analytics over the same stream --------------------------
    mark = meter.mark()
    eng, build_s = timed(lambda: build_sharded_analytics(
        toks, sigma, shard_bits=16))
    analytics_build = {"build_s": build_s, **meter.since(mark)}
    rng = np.random.default_rng(seed + 2)
    lo, hi = make_range_queries(rng, n, 64)
    loj, hij = jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32)
    ops = {}
    for name, fn in (
            ("topk", jax.jit(lambda e, a, b: e.range_topk(a, b, TOPK_K))),
            ("distinct", jax.jit(lambda e, a, b: e.range_distinct(a, b)))):
        mark = meter.mark()
        out, first_s = timed(fn, eng, loj, hij)
        _, steady_s = timed(fn, eng, loj, hij)
        results[name] = jax.tree.map(np.asarray, out)
        ops[name] = {"first_s": first_s, "steady_s": steady_s, "batch": 64,
                     **meter.since(mark)}
    wide_verified = 0
    syms, cnts = results["topk"]
    for i in range(64):
        bc = np.bincount(toks[lo[i]:hi[i]], minlength=sigma)
        if int(results["distinct"][i]) != int(np.count_nonzero(bc)):
            raise AssertionError(f"distinct query {i}")
        got = syms[i] >= 0
        want_top = np.sort(bc[bc > 0])[::-1][:TOPK_K]
        if not (np.array_equal(cnts[i][got], want_top)
                and np.array_equal(bc[syms[i][got]], cnts[i][got])):
            raise AssertionError(f"top-k query {i}")
        wide_verified += 2
    wide = {"build": analytics_build, **ops, "verified": wide_verified}
    return {"phase": "byte_text", "n": n, "sigma": sigma, "shard_bits": 16,
            "num_shards": idx.num_shards, "search": search,
            "analytics": wide, "verified": verified + wide_verified,
            "peak_bytes_in_use": peak_bytes()}


def phase_four_chips(log2n: int, sigma: int, seed: int,
                     meter: CompileMeter) -> dict:
    """The only multi-chip code: the pmap(vmap(build)) shard build."""
    import jax
    from repro.analytics.engine import build_sharded_analytics
    from repro.data import make_corpus

    n = 1 << log2n
    toks = np.asarray(make_corpus(n, sigma, seed=seed), np.int64)
    mark = meter.mark()
    eng, pmap_s = timed(lambda: build_sharded_analytics(
        toks, sigma, shard_bits=16))
    pmap_build = {"build_s": pmap_s, **meter.since(mark)}
    log(f"four chips: pmap build of {eng.num_shards} shards in "
        f"{pmap_s:.1f} s")
    leaf = jax.tree.leaves(eng.shards)[0]
    placement = {"sharding": type(leaf.sharding).__name__,
                 "devices": sorted(str(d) for d in leaf.devices())}
    log(f"four chips: pmap-built leaves land on {placement}")
    mark = meter.mark()
    ref, loop_s = timed(lambda: build_sharded_analytics(
        toks, sigma, shard_bits=16, parallel=False))
    loop_build = {"build_s": loop_s, **meter.since(mark)}
    a_leaves, a_def = jax.tree.flatten(eng)
    b_leaves, b_def = jax.tree.flatten(ref)
    if a_def != b_def:
        raise AssertionError(f"tree structure differs: {a_def} vs {b_def}")
    for a, b in zip(a_leaves, b_leaves):
        if a.dtype != b.dtype or not np.array_equal(np.asarray(a),
                                                    np.asarray(b)):
            raise AssertionError("pmap-built store differs from the loop "
                                 "build")
    served = serve_count_quantile(eng, toks, sigma, seed + 1, meter)
    return {"phase": "four_chips", "n": n, "sigma": sigma, "shard_bits": 16,
            "num_shards": eng.num_shards, "pmap_build": pmap_build,
            "loop_build": loop_build, "placement": placement,
            "leaves_identical": len(a_leaves), **served,
            "peak_bytes_in_use": peak_bytes()}


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip shard-build phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--vocab-log2n", type=int, default=26,
                    help="log2 tokens of the vocabulary-sized store")
    ap.add_argument("--text-log2n", type=int, default=24,
                    help="log2 tokens of the byte-alphabet text")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"devices: {devices}")
    log(f"device: {json.dumps(device)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{dev.platform!r}); this run needs a TPU chip",
              file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    meter = CompileMeter()
    sigma = 1 << 17
    if args.vocab_log2n != 26 or args.text_log2n != 24:
        log(f"sizes cut: vocab store n=2^{args.vocab_log2n} (full 2^26), "
            f"text n=2^{args.text_log2n} (full 2^24)")
    phases = []
    if args.chips == 4:
        phases.append(lambda: phase_four_chips(args.vocab_log2n, sigma,
                                               args.seed, meter))
    else:
        phases.append(lambda: phase_vocab_store(args.vocab_log2n, sigma,
                                                args.seed, meter))
        phases.append(lambda: phase_byte_text(args.text_log2n,
                                              args.seed + 10, meter))
    for run in phases:
        t0 = time.perf_counter()
        rec = run()
        rec["phase_s"] = time.perf_counter() - t0
        log("phase " + json.dumps(rec))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
