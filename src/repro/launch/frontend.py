"""Query front-end CLI: drive a bursty request trace at the overload-
hardened serving front-end and report shed/degrade/latency behaviour.

(The *model*-serving CLI — prefill + decode — is ``repro.launch.serve``;
this CLI exercises ``repro.serving.QueryFrontend``, the analytics query
front-end.)

PYTHONPATH=src python -m repro.launch.frontend --smoke
PYTHONPATH=src python -m repro.launch.frontend --overload 5.0 \\
    --requests 2000 --deadline-ms 50
PYTHONPATH=src python -m repro.launch.frontend --smoke \\
    --record-trace /tmp/burst.jsonl                # record the trace
PYTHONPATH=src python -m repro.launch.frontend --smoke \\
    --replay /tmp/burst.jsonl --overload 5.0       # replay it 5× faster

The trace is a bursty arrival process (quiet base load with periodic
storm windows, seeded) of mixed count/quantile/top-k queries; ``--replay``
drives a recorded trace instead, and ``--overload X`` time-compresses
either by X (the same requests offered X× faster). Submission is paced on
the shared ``robust.Clock`` with catch-up semantics: if the submitter
falls behind schedule it submits immediately rather than silently
thinning the offered load.

``--metrics-dir`` exports the ``serve.frontend.*`` gauges/counters and
per-op latency histograms for ``repro.launch.obs`` (gate with
``--slo 'frontend.*:p99_ms<=...'``); ``--profile-dir`` wraps serving in a
``jax.profiler`` device trace.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import numpy as np

from repro import obs
from repro.analytics.engine import build_sharded_analytics
from repro.data import make_corpus
from repro.ingest.serving import GenerationServer
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import (BreakerConfig, FrontendConfig, QueryFrontend,
                           ShedError)


def make_trace(n: int, requests: int, seed: int, *, base_qps: float,
               burst_qps: float, burst_every_s: float, burst_len_s: float,
               deadline_s: float, topk_k: int) -> list:
    """Bursty arrival schedule: quiet base load punctuated by storm
    windows. Returns [{t, op, lo, hi, k, deadline_s}, ...] sorted by t."""
    rng = np.random.default_rng(seed)
    events, t = [], 0.0
    ops = ("count", "quantile", "topk")
    while len(events) < requests:
        in_burst = (t % burst_every_s) < burst_len_s
        rate = burst_qps if in_burst else base_qps
        t += float(rng.exponential(1.0 / rate))
        lo = int(rng.integers(0, max(1, n - 1)))
        hi = int(rng.integers(lo + 1, n + 1))
        op = ops[int(rng.integers(0, len(ops)))]
        events.append({
            "t": round(t, 6), "op": op, "lo": lo, "hi": hi,
            "k": (int(rng.integers(0, hi - lo)) if op == "quantile"
                  else (topk_k if op == "topk" else None)),
            "deadline_s": deadline_s,
        })
    return events


def load_trace(path: str) -> list:
    return [json.loads(ln) for ln in Path(path).read_text().splitlines()
            if ln.strip()]


def save_trace(path: str, trace: list) -> None:
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    Path(path).write_text("".join(json.dumps(e) + "\n" for e in trace))


def drive(fe: QueryFrontend, trace: list, overload: float, sigma: int):
    """Paced catch-up submission of the (time-compressed) trace; returns
    the tickets in submission order."""
    clock = fe.clock
    t0 = clock.now()
    tickets = []
    for ev in trace:
        target = t0 + ev["t"] / max(overload, 1e-9)
        lag = target - clock.now()
        if lag > 0:
            clock.sleep(lag)         # on schedule; behind ⇒ submit now
        kw = {"deadline_s": ev.get("deadline_s")}
        if ev["op"] == "quantile":
            kw["k"] = ev["k"]
        elif ev["op"] == "count":
            kw["sym_lo"], kw["sym_hi"] = 0, sigma
        tickets.append(fe.submit(ev["op"], ev["lo"], ev["hi"], **kw))
    return tickets


def report(fe: QueryFrontend, tickets: list, sw: obs.Stopwatch) -> dict:
    """Wait for every ticket; ``sw`` has been lapped at submit start so
    the final lap spans submit→last-result (the q/s denominator)."""
    lats, degraded, misses, served, shed = [], 0, 0, 0, 0
    for t in tickets:
        try:
            a = t.result(timeout=30.0)
        except ShedError:
            shed += 1
            continue
        served += 1
        lats.append(a.latency_s)
        degraded += bool(a.degraded)
        misses += not a.deadline_met
    wall_s = sw.lap()
    out = {
        "offered": len(tickets),
        "served": served,
        "shed": shed,
        "shed_rate": shed / max(1, len(tickets)),
        "degraded": degraded,
        "deadline_misses": misses,
        "qps": served / max(wall_s, 1e-9),
        "p50_ms": float(np.percentile(lats, 50)) * 1e3 if lats else 0.0,
        "p99_ms": float(np.percentile(lats, 99)) * 1e3 if lats else 0.0,
        "final_level": fe.ladder.level,
    }
    obs.gauge("serve.frontend.qps").set(out["qps"])
    obs.gauge("serve.frontend.shed_rate").set(out["shed_rate"])
    return out


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized corpus + short trace")
    ap.add_argument("--n", type=int, default=1 << 16)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--shard-bits", type=int, default=12)
    ap.add_argument("--requests", type=int, default=1000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--overload", type=float, default=1.0,
                    help="time-compress the trace by this factor "
                         "(5.0 ⇒ the same requests offered 5× faster)")
    ap.add_argument("--base-qps", type=float, default=200.0)
    ap.add_argument("--burst-qps", type=float, default=2000.0)
    ap.add_argument("--deadline-ms", type=float, default=250.0)
    ap.add_argument("--capacity", type=int, default=256)
    ap.add_argument("--topk-k", type=int, default=8)
    ap.add_argument("--replay", type=str, default=None,
                    help="drive a recorded trace (JSONL) instead of "
                         "generating one")
    ap.add_argument("--record-trace", type=str, default=None,
                    help="write the generated trace here (JSONL) for "
                         "later --replay")
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
        args.n = min(args.n, 1 << 13)
        args.vocab = min(args.vocab, 64)
        args.shard_bits = min(args.shard_bits, 10)
        args.requests = min(args.requests, 300)

    toks = np.asarray(make_corpus(args.n, args.vocab, seed=args.seed),
                      np.int64)
    sw = obs.Stopwatch()
    eng = build_sharded_analytics(toks, args.vocab,
                                  shard_bits=args.shard_bits)
    eng.probe_shard(0)   # compile the liveness probe before the circuit
    #                      breakers put it under a timeout
    print(f"engine: {args.n} tokens, {eng.num_shards} shards "
          f"in {sw.lap():.2f}s")

    if args.replay:
        trace = load_trace(args.replay)
        print(f"replaying {len(trace)} requests from {args.replay} "
              f"at {args.overload:.1f}× speed")
    else:
        trace = make_trace(args.n, args.requests, args.seed,
                           base_qps=args.base_qps,
                           burst_qps=args.burst_qps,
                           burst_every_s=2.0, burst_len_s=0.5,
                           deadline_s=args.deadline_ms / 1e3,
                           topk_k=args.topk_k)
        if args.record_trace:
            save_trace(args.record_trace, trace)
            print(f"trace → {args.record_trace} ({len(trace)} requests)")

    fe = QueryFrontend(
        GenerationServer(eng),
        config=FrontendConfig(
            capacity=args.capacity, topk_k=args.topk_k,
            # smoke keeps the compile surface small: every (op, level,
            # bucket) variant is warmed below, and each bucket is 6 more
            # compiles
            buckets=(8, 32) if args.smoke else (8, 32, 128),
            # real-clock probe timings: the library defaults (50ms
            # logical deadline, 250ms interval) are sized for FakeClock
            # chaos tests; a real CPU probe costs tens of ms, so keep a
            # healthy margin or every breaker opens spuriously.
            breaker=BreakerConfig(probe_timeout_s=2.0,
                                  probe_interval_s=5.0,
                                  reset_after_s=2.0)))
    # Warm the jit cache (every op × bucket at the exact level, plus the
    # degraded variants at the smallest bucket), then re-seed the
    # admission EWMA from a steady-state batch: warmup pumps feed
    # compile-dominated service times into the EWMA, which would
    # otherwise shed the whole trace as over_budget before it starts.
    # Metrics are off for the whole block so the exported latency
    # histograms (and the --slo gate reading them) see only the trace.
    with obs.disabled():
        warm = (("count", {"sym_hi": args.vocab}),
                ("quantile", {"k": 0}), ("topk", {}))
        for op, kw in warm:
            for bucket in fe.config.buckets:
                for _ in range(bucket):
                    fe.submit(op, 0, args.n, deadline_s=600.0, **kw)
                while fe.queue.depth:
                    fe.pump()
                # the degraded variants must be warm at every bucket too:
                # one mid-burst compile stalls the pump for seconds
                for level in (1, 2):
                    mode, fn = fe._op_fn(op, level)
                    fe.runner.run((op, level), fn, eng,
                                  np.zeros((4, bucket), np.int32), bucket)
        compiled, warm_s = fe.runner.compiled, sw.lap()
        batch = fe.runner.max_batch
        steady_s = 0.0
        for _ in range(2):       # first batch may absorb a probe refresh
            for _ in range(batch):
                fe.submit("count", 0, args.n, deadline_s=600.0,
                          sym_hi=args.vocab)
            sw.lap()
            fe.pump()
            steady_s = sw.lap()
        for _ in range(30):
            fe.queue.observe_service(steady_s, batch)
    print(f"warmup: {compiled} variants compiled in {warm_s:.2f}s "
          f"(steady batch {steady_s * 1e3:.2f}ms)")

    obs.start_trace(args.profile_dir)
    fe.start()
    sw.lap()
    with obs.span("frontend.drive", requests=len(trace),
                  overload=args.overload):
        tickets = drive(fe, trace, args.overload, args.vocab)
        out = report(fe, tickets, sw)
    fe.stop(drain=True)
    if obs.stop_trace():
        print(f"device trace → {args.profile_dir}")

    print(f"offered {out['offered']} requests "
          f"({args.overload:.1f}× pacing): served {out['served']} "
          f"({out['qps']:.0f} q/s), shed {out['shed']} "
          f"({out['shed_rate']:.0%}), {out['degraded']} degraded, "
          f"{out['deadline_misses']} deadline misses")
    print(f"accepted latency p50 {out['p50_ms']:.2f}ms / "
          f"p99 {out['p99_ms']:.2f}ms; final degrade level "
          f"{out['final_level']}; shed reasons "
          f"{fe.stats()['shed']}")
    if args.metrics_dir:
        obs.write_snapshot()
        print(f"metrics → {args.metrics_dir}")


if __name__ == "__main__":
    main()
