"""Range-analytics CLI: build a sharded analytics store over the synthetic
corpus and serve a batched mixed query stream with per-op reporting.

PYTHONPATH=src python -m repro.launch.analytics --smoke
PYTHONPATH=src python -m repro.launch.analytics --n 524288 --vocab 4096 \
    --shard-bits 14 --queries 1024
PYTHONPATH=src python -m repro.launch.analytics --smoke --metrics-dir /tmp/m
PYTHONPATH=src python -m repro.launch.obs /tmp/m     # then inspect

Build: wavelet-matrix shards via the paper's τ-chunked construction
(pmap/vmap over the mesh when devices allow — ``data.shard_build``).
Serve: each op is one jitted function vmapped over the query batch and
fanned across shards; a 1024-query mixed stream compiles each op once
(shapes are static) and reports per-op latency + queries/s. A sample of
every op is verified against numpy on the regenerated raw stream.

``--metrics-dir`` captures the run through ``repro.obs``: per-op
``serve.analytics.*`` latency histograms / q/s / compile cost, build and
restore spans, path-selection counters, and a JSONL event log — rendered
by ``repro.launch.obs``. Serving ops additionally run under
``obs.profiled_op``, so the snapshot carries the ``prof.*`` cost-model
gauges (FLOPs, bytes, roofline utilization, peak working set) per op;
``--profile-dir`` wraps the serving section in a ``jax.profiler`` device
trace.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analytics import (build_sharded_analytics, load_analytics,
                             save_analytics, snapshot_meta)
from repro.data import make_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def make_queries(n: int, num: int, seed: int):
    """(lo, hi, k) batches: mixed narrow/wide ranges over the corpus."""
    rng = np.random.default_rng(seed)
    lo = rng.integers(0, max(1, n - 1), num).astype(np.int32)
    width = np.where(rng.random(num) < 0.5,
                     rng.integers(1, 256, num),
                     rng.integers(256, max(512, n // 4), num))
    hi = np.minimum(lo + width, n).astype(np.int32)
    k = rng.integers(0, np.maximum(hi - lo, 1)).astype(np.int32)
    return lo, hi, k


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized build + query + verification")
    ap.add_argument("--n", type=int, default=1 << 18)
    ap.add_argument("--vocab", type=int, default=4096)
    ap.add_argument("--shard-bits", type=int, default=14)
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--topk", type=int, default=8)
    ap.add_argument("--verify", type=int, default=16,
                    help="# of queries per op to check against numpy")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--snapshot-dir", type=str, default=None,
                    help="persisted analytics snapshot: restore from here "
                         "when present (skipping the build), else build "
                         "and save here")
    ap.add_argument("--metrics-dir", type=str, default=None,
                    help="export obs metrics snapshot + JSONL events here "
                         "(inspect with `python -m repro.launch.obs`)")
    ap.add_argument("--profile-dir", type=str, default=None,
                    help="capture a jax.profiler device trace of the "
                         "serving section into this directory")
    args = ap.parse_args()
    if args.metrics_dir:
        obs.configure(args.metrics_dir)
    if args.smoke:
        args.n = min(args.n, 1 << 14)
        args.vocab = min(args.vocab, 512)
        args.shard_bits = min(args.shard_bits, 12)
        args.queries = min(args.queries, 256)

    toks = np.asarray(make_corpus(args.n, args.vocab, seed=args.seed),
                      np.int64)

    sw = obs.Stopwatch()
    restored = False
    save_snapshot = bool(args.snapshot_dir)
    if args.snapshot_dir:
        # probe meta.json BEFORE restoring arrays: geometry AND corpus
        # identity (seed) must match what this invocation will verify
        # against, else a stale snapshot would serve the wrong corpus
        try:
            meta = snapshot_meta(args.snapshot_dir)
            got = (meta["n"], meta["sigma"], meta["shard_bits"],
                   meta.get("corpus_seed"))
            want = (args.n, args.vocab, args.shard_bits, args.seed)
            if got == want:
                # verified restore; derived-leaf corruption is repaired
                # in place, primary corruption raises → rebuild below
                eng = load_analytics(args.snapshot_dir)
                restored = True
            else:
                print(f"snapshot (n, vocab, shard_bits, seed)={got} does "
                      f"not match requested {want} — rebuilding")
        except FileNotFoundError:
            pass
        except ValueError as e:
            # foreign checkpoint in the directory: rebuild, but never
            # overwrite someone else's data with our snapshot
            print(f"ignoring --snapshot-dir: {e}")
            save_snapshot = False
        except Exception as e:
            # unusable snapshot (unrepairable corruption, torn write,
            # missing leaves, …): warn and rebuild from source — a bad
            # snapshot must never take serving down
            print(f"WARNING: snapshot restore failed ({type(e).__name__}: "
                  f"{e}) — rebuilding from source")
    if not restored:
        from repro.robust import with_retry
        with obs.span("analytics.build", n=args.n, vocab=args.vocab,
                      shard_bits=args.shard_bits) as sp:
            eng = sp.sync(with_retry(
                lambda: build_sharded_analytics(toks, args.vocab,
                                                shard_bits=args.shard_bits),
                retries=2, backoff_s=0.1,
                on_retry=lambda a, e: print(
                    f"build attempt {a + 1} failed ({e}) — retrying")))
    jax.block_until_ready(jax.tree.leaves(eng.shards)[0])
    t_build = sw.lap()
    obs.gauge("serve.analytics.build_s").set(t_build)
    obs.gauge("serve.analytics.tokens_per_s").set(args.n / max(t_build,
                                                               1e-9))
    verb = "restore" if restored else "build"
    print(f"{verb}: {args.n} tokens, vocab {args.vocab}, "
          f"{eng.num_shards} shards of {eng.shard_size} in {t_build:.2f}s "
          f"({args.n / t_build / 1e3:.0f} ktok/s, "
          f"{eng.bits_per_token():.1f} bits/token, "
          f"{jax.local_device_count()} device(s))")
    if save_snapshot and not restored:
        path = save_analytics(eng, args.snapshot_dir,
                              extra_meta={"corpus_seed": args.seed})
        print(f"snapshot saved → {path}")

    lo, hi, k = make_queries(args.n, args.queries, args.seed + 1)
    loj, hij, kj = jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(k)
    sym_lo = jnp.asarray(lo % args.vocab, jnp.int32)
    sym_hi = jnp.minimum(sym_lo + 64, args.vocab)
    B = args.queries
    obs.gauge("serve.analytics.coverage").set(float(eng.coverage(0, args.n)))

    # cost-model profile of the construction path (one shard-sized build),
    # so the snapshot relates build time to the hardware roofline
    # alongside the serving ops below
    from repro.core.wavelet_matrix import build_wavelet_matrix
    shard0 = jnp.asarray(toks[:eng.shard_size], jnp.int32)
    _, cstats = obs.profile_op(
        "analytics.construct_shard",
        lambda s: build_wavelet_matrix(s, eng.sigma),
        shard0, work_elements=float(eng.shard_size))
    if "roofline_util" in cstats:
        print(f"construct_shard: roofline {cstats['roofline_util']:.1%} "
              f"({cstats.get('bound', '?')}-bound)")

    mesh_ctx = jax.set_mesh(make_host_mesh())
    with mesh_ctx, obs.span("analytics.serve", queries=B), \
            obs.trace(args.profile_dir):
        ops = {
            "quantile": (jax.jit(lambda e, a, b, c: e.range_quantile(a, b, c)),
                         (eng, loj, hij, kj)),
            "count": (jax.jit(lambda e, a, b, s0, s1:
                              e.range_count(a, b, s0, s1)),
                      (eng, loj, hij, sym_lo, sym_hi)),
            "topk": (jax.jit(lambda e, a, b: e.range_topk(a, b, args.topk)),
                     (eng, loj, hij)),
            "distinct": (jax.jit(lambda e, a, b: e.range_distinct(a, b)),
                         (eng, loj, hij)),
        }
        results = {}
        for name, (fn, fargs) in ops.items():
            out, t, t_c = obs.profiled_op("analytics", name, fn, *fargs,
                                          batch=B)
            results[name] = out
            print(f"{name}: {B} queries in {t * 1e3:.1f} ms "
                  f"({B / t:.0f} q/s; compile {t_c:.2f}s)")
    if args.profile_dir:
        print(f"device trace → {args.profile_dir}")

    bad = 0
    nv = min(args.verify, B)
    for i in range(nv):
        sl = toks[lo[i]:hi[i]]
        want_q = np.sort(sl)[k[i]] if len(sl) else -1
        if int(np.asarray(results["quantile"])[i]) != want_q:
            bad += 1
            print(f"  QUANTILE MISMATCH query {i}")
        want_c = int(((sl >= int(sym_lo[i])) & (sl < int(sym_hi[i]))).sum())
        if int(np.asarray(results["count"])[i]) != want_c:
            bad += 1
            print(f"  COUNT MISMATCH query {i}")
        if int(np.asarray(results["distinct"])[i]) != len(np.unique(sl)):
            bad += 1
            print(f"  DISTINCT MISMATCH query {i}")
        bc = np.bincount(sl, minlength=args.vocab)
        want_top = np.sort(bc[bc > 0])[::-1][:args.topk]
        syms_i = np.asarray(results["topk"][0])[i]
        cnts_i = np.asarray(results["topk"][1])[i]
        if not np.array_equal(cnts_i[syms_i >= 0], want_top):
            bad += 1
            print(f"  TOPK MISMATCH query {i}")
    if bad:
        raise SystemExit(f"{bad} verification failures")
    print(f"verified {nv} samples of each op against numpy ✓")
    if args.metrics_dir:
        obs.write_snapshot()
        print(f"metrics → {args.metrics_dir}")


if __name__ == "__main__":
    main()
