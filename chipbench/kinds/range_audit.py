"""Open loop of range-analytics requests through the query front-end.

Independent audit users send count and quantile requests on a Poisson
schedule at a fixed ``rate_per_s``; each is timed from its due time to
its answer, so a stall delays every request due behind it. Both ends are
read on the benchmark's clock: the due time from the window's start, the
answer when the benchmark's pump thread sees its ticket resolved after a
``pump()`` returns (the program's own ``Answer.latency_s`` is not used).

Traffic keys: ``rate_per_s``, ``count_share`` (the rest are quantiles),
``narrow_share`` / ``narrow_max`` (position-range widths),
``deadline_s``, ``capacity``, ``buckets``, ``probe_timeout_s``,
``probe_interval_s``, ``reset_after_s`` (the front-end's settings),
``answer_wait_s`` (how long past the window's close an answer may come),
``verify_sample``. Configuration keys: the Zipf stream (``n_tokens``,
``sigma``, ``zipf_exponent``, ``doc_len``, ``eos_id``, ``data_seed``)
and the store's build settings (``shard_bits``, ``tau``, ``big_step``,
``select_sample_rate``).

The store is the deployment's corpus, made from the configuration's
``data_seed``; the seed of the run draws the requests. It is built once
per checkout and program and restored after that through the program's
own snapshot path (``repro.analytics.snapshot``).
"""
from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass

import numpy as np

from chipbench import cache, data, reference

EXACT, DEGRADED, SHED, ERROR, MISSING = range(5)


def corpus(cfg: dict) -> np.ndarray:
    return data.zipf_tokens(cfg["data_seed"], 0, cfg["n_tokens"],
                            cfg["sigma"], cfg["zipf_exponent"],
                            cfg["doc_len"], cfg["eos_id"])


def cached_store(ctx, toks: np.ndarray):
    """The program's store of ``toks``: restored from the structure cache,
    or built and saved there."""
    from repro.analytics.engine import build_sharded_analytics
    from repro.analytics.snapshot import load_analytics, save_analytics
    cfg = ctx.config
    where = ctx.cache_dir / cache.key(cfg, "store")
    mark, t0 = ctx.meter.mark(), time.perf_counter()
    if where.exists():
        with ctx.span("restore"):
            store = load_analytics(where)
        how = "restore"
    else:
        with ctx.span("build"):
            store = build_sharded_analytics(
                toks, cfg["sigma"], shard_bits=cfg["shard_bits"],
                tau=cfg["tau"], big_step=cfg["big_step"],
                sample_rate=cfg["select_sample_rate"])
        import jax
        jax.block_until_ready(store)
        save_analytics(store, where)
        how = "build+save"
    print(f"setup store {how}: {time.perf_counter() - t0:.3f} s "
          f"{ctx.meter.since(mark)}", flush=True)
    return store


def schedule(seed: int, tr: dict, n: int, sigma: int, seconds: float):
    """Requests due in [0, seconds): due time, op, lo, hi, a, b (count
    symbols [a, b)) and k (quantile rank), all drawn from ``seed``."""
    g = data.rng(seed, 2)
    rate = tr["rate_per_s"]
    gaps = g.exponential(1.0 / rate, int(rate * seconds * 1.5) + 64)
    due = np.cumsum(gaps)
    num = int(np.searchsorted(due, seconds))
    due = due[:num]
    count = g.random(num) < tr["count_share"]
    lo, hi = data.range_queries(g, n, num, tr["narrow_share"],
                                tr["narrow_max"])
    a = g.integers(0, sigma, num)
    b = a + 1 + (g.random(num) * (sigma - a)).astype(np.int64)
    k = (g.random(num) * (hi - lo)).astype(np.int64)
    return {"due": due, "count": count, "lo": lo, "hi": hi, "a": a, "b": b,
            "k": k}


def make_frontend(ctx, store):
    from repro.ingest.serving import GenerationServer
    from repro.serving import BreakerConfig, FrontendConfig, QueryFrontend
    tr = ctx.traffic
    return QueryFrontend(GenerationServer(store), config=FrontendConfig(
        buckets=tuple(tr["buckets"]), capacity=tr["capacity"],
        default_deadline_s=tr["deadline_s"],
        breaker=BreakerConfig(probe_timeout_s=tr["probe_timeout_s"],
                              probe_interval_s=tr["probe_interval_s"],
                              reset_after_s=tr["reset_after_s"])))


def submit(fe, req: dict, i: int, deadline_s=None):
    lo, hi = int(req["lo"][i]), int(req["hi"][i])
    if req["count"][i]:
        return fe.submit("count", lo, hi, sym_lo=int(req["a"][i]),
                         sym_hi=int(req["b"][i]), deadline_s=deadline_s)
    return fe.submit("quantile", lo, hi, k=int(req["k"][i]),
                     deadline_s=deadline_s)


def warm(ctx, fe, store, n: int, sigma: int) -> None:
    """Compile every (op, bucket) the window can use at the exact level,
    then seed the admission's service estimate from steady batches, as
    the front-end CLI does (warm-up compiles would otherwise make it
    shed the window's first requests)."""
    mark, t0 = ctx.meter.mark(), time.perf_counter()
    store.probe_shard(0)
    for op, kw in (("count", {"sym_hi": sigma}), ("quantile", {"k": 0})):
        for bucket in fe.config.buckets:
            for _ in range(bucket):
                fe.submit(op, 0, n, deadline_s=600.0, **kw)
            while fe.queue.depth:
                fe.pump()
    batch = fe.runner.max_batch
    steady = []
    for op, kw in (("count", {"sym_hi": sigma}), ("quantile", {"k": 0})):
        for _ in range(batch):
            fe.submit(op, 0, n, deadline_s=600.0, **kw)
        t1 = time.perf_counter()
        fe.pump()
        steady.append(time.perf_counter() - t1)
    for _ in range(30):
        fe.queue.observe_service(float(np.mean(steady)), batch)
    # every run's window starts just after a refresh that probed every
    # shard, so the window holds the same number of probe stalls
    t1 = time.perf_counter()
    fe.breakers.resize(fe.breakers.num_shards)
    fe.breakers.refresh()
    print(f"setup warm: {time.perf_counter() - t0:.3f} s, steady batch "
          f"{[round(s, 4) for s in steady]} s, probe refresh "
          f"{time.perf_counter() - t1:.3f} s {ctx.meter.since(mark)}",
          flush=True)


@dataclass
class State:
    cfg: dict
    tr: dict
    toks: np.ndarray
    fe: object
    req: dict
    bits_per_token: float
    seed: int


def setup(ctx) -> State:
    import jax
    cfg, tr = ctx.config, ctx.traffic
    t0 = time.perf_counter()
    toks = corpus(cfg)
    print(f"setup data: {len(toks)} tokens in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    if "frontend" in ctx.hooks:
        fe, bits = ctx.hooks["frontend"](ctx, toks), 0.0
    else:
        store = cached_store(ctx, toks)
        bits = 8.0 * sum(l.size * l.dtype.itemsize
                         for l in jax.tree.leaves(store)) / store.n
        fe = make_frontend(ctx, store)
        warm(ctx, fe, store, len(toks), cfg["sigma"])
    req = schedule(ctx.seed, tr, len(toks), cfg["sigma"], ctx.seconds)
    return State(cfg, tr, toks, fe, req, bits, ctx.seed)


def window(st: State, seconds: float, ctx) -> dict:
    from repro.serving import ShedError
    fe, tr = st.fe, st.tr
    req = st.req
    num = len(req["due"])
    stop = threading.Event()
    pumps = []                                  # (seconds, served)
    lock = threading.Lock()
    pending = {}                                # index -> unresolved ticket
    answered_t = np.full(num, np.inf)           # seconds after the start
    start = time.monotonic()

    def stamp():
        """Time every ticket resolved since the last look."""
        now = time.monotonic() - start
        with lock:
            for i in [i for i, t in pending.items() if t.done()]:
                answered_t[i] = now
                del pending[i]

    def pump_loop():
        while not stop.is_set() or fe.queue.depth:
            if not fe.queue.depth:
                time.sleep(1e-3)
                continue
            t0 = time.perf_counter()
            with ctx.span("pump"):
                served = fe.pump()
            pumps.append((time.perf_counter() - t0, served))
            stamp()

    worker = threading.Thread(target=pump_loop, name="chipbench-pump",
                              daemon=True)
    worker.start()
    tickets, sub_t, depth = [None] * num, np.zeros(num), np.zeros(num)
    for i in range(num):
        wait = start + req["due"][i] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        sub_t[i] = time.monotonic() - start
        depth[i] = fe.queue.depth
        tickets[i] = t = submit(fe, req, i)
        with lock:
            if t.done():                        # shed or answered at once
                answered_t[i] = time.monotonic() - start
            else:
                pending[i] = t
    close = start + seconds
    status = np.full(num, MISSING)
    values = [None] * num
    for i, t in enumerate(tickets):
        try:
            a = t.result(timeout=max(0.0, close + tr["answer_wait_s"]
                                     - time.monotonic()))
        except ShedError:
            status[i] = SHED
            continue
        except TimeoutError:
            continue
        except Exception:                               # noqa: BLE001
            status[i] = ERROR
            continue
        values[i] = a.value
        status[i] = DEGRADED if a.degraded else EXACT
    stop.set()
    worker.join(timeout=tr["answer_wait_s"])
    stamp()
    latency = np.where(status == EXACT, answered_t - req["due"], np.inf)
    served = [p for p in pumps if p[1]]
    return {"requests": num, "window_s": seconds, "exact": int(np.sum(status == EXACT)),
            "degraded": int(np.sum(status == DEGRADED)),
            "shed": int(np.sum(status == SHED)),
            "errors": int(np.sum(status == ERROR)),
            "missing": int(np.sum(status == MISSING)),
            "batches": len(served),
            "pump_s_mean": float(np.mean([p[0] for p in served]))
            if served else None,
            "late_s_mean": float(np.mean(sub_t - req["due"])) if num else 0.0,
            "late_s_max": float(np.max(sub_t - req["due"])) if num else 0.0,
            "depth_quarters": [float(np.mean(q)) if len(q) else 0.0
                               for q in np.array_split(depth, 4)],
            "_status": status, "_latency": latency, "_values": values}


def p95_ms(latency_s: np.ndarray, cap_s: float) -> float:
    """Nearest-rank 95th percentile of all requests' latencies; a miss
    (+inf) that the percentile lands on reads as ``cap_s``, the longest
    wait the window allows."""
    lat = np.sort(latency_s)
    v = lat[max(0, math.ceil(0.95 * len(lat)) - 1)]
    return 1e3 * (cap_s if not np.isfinite(v) else float(v))


def metrics(st: State, res: dict) -> dict:
    cap = res["window_s"] + st.tr["answer_wait_s"]
    return {"query_p95_ms": p95_ms(res["_latency"], cap),
            "bits_per_token": st.bits_per_token}


def check(st: State, res: dict, ctx):
    """A seeded sample of the answers (the widest always among them)
    against the raw stream; every request due in the window must have
    been answered or explicitly shed."""
    status, values, req = res.pop("_status"), res.pop("_values"), st.req
    st.fe.breakers.close_pool()
    st.fe = None                                  # free the program's state
    answered = np.flatnonzero((status == EXACT) | (status == DEGRADED))
    g = data.rng(st.seed, 3)
    pick = set(g.choice(answered, min(len(answered),
                                      st.tr["verify_sample"]),
                        replace=False).tolist()) if len(answered) else set()
    if len(answered):
        pick.add(int(answered[np.argmax((req["hi"] - req["lo"])[answered])]))
    print(f"check: {len(pick)} of {len(answered)} answers against the raw "
          f"stream", flush=True)
    wrong = 0
    for i in sorted(pick):
        lo, hi = int(req["lo"][i]), int(req["hi"][i])
        if req["count"][i]:
            want = reference.range_count(st.toks, lo, hi, int(req["a"][i]),
                                         int(req["b"][i]))
        else:
            want = reference.range_quantile(st.toks, lo, hi,
                                            int(req["k"][i]),
                                            st.cfg["sigma"])
        got = values[i]
        if status[i] == EXACT:
            wrong += int(got != want)
        else:
            wrong += int(not (got[0] <= want <= got[1]))
    checks = {"wrong_answers": {"value": wrong, "limit": 0},
              "never_answered": {"value": int(res["missing"]), "limit": 0}}
    failed = int(res["requests"] - res["exact"])
    return int(res["requests"]), failed, checks
