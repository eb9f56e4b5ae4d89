"""The controls: the reference put in the program's place with one
guarantee of the configuration broken, driven through a whole run.
Every control run must come out ``correct: false``; its compared numbers
are the upper readings the limits sit below.

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3 --seconds 5

* construction (``bulk_build``): level bitmaps from a partition that is
  not stable — the ones come out in reverse order;
* range analytics (``range_audit``): quantiles from a descent cut two
  levels short (the front-end's first degraded rung) served as exact;
* full-text search (``pattern_search``): counts that drop the matches
  crossing a shard seam.

The benchmark's own runs never run these.
"""
from __future__ import annotations

import argparse
import json
import sys
from types import SimpleNamespace

import numpy as np

from chipbench import reference
from chipbench.kinds import bulk_build


class _Ticket:
    def __init__(self, answer):
        self._answer = answer

    def done(self):
        return True

    def result(self, timeout=None):
        return self._answer


class ControlFrontend:
    """Answers each request on submission from the raw stream, with the
    quantile descent cut ``shave`` levels short."""

    def __init__(self, toks: np.ndarray, sigma: int, shave: int = 2):
        self.toks, self.sigma, self.shave = toks, sigma, shave
        self.queue = SimpleNamespace(depth=0)
        self.breakers = SimpleNamespace(close_pool=lambda: None)

    def submit(self, op, lo, hi, *, sym_lo=0, sym_hi=None, k=None,
               deadline_s=None):
        if op == "count":
            v = reference.range_count(self.toks, lo, hi, sym_lo, sym_hi)
        else:
            v = reference.range_quantile(self.toks, lo, hi, k, self.sigma,
                                         shave=self.shave)
        return _Ticket(SimpleNamespace(value=v, degraded=False))


def hooks(kind: str, config: dict) -> dict:
    """The control of a traffic kind, as ``run_cell`` hooks."""
    if kind == "bulk_build":
        n = 1 << config["shard_bits"]

        def build(tokens):
            return reference.wavelet_matrix(
                np.asarray(tokens).reshape(-1, n),
                bulk_build.nbits_of(config["sigma"]),
                config["select_sample_rate"], stable=False)
        return {"build": build, "leaves": lambda d: d}
    if kind == "range_audit":
        return {"frontend": lambda ctx, toks: ControlFrontend(
            toks, config["sigma"])}
    if kind == "pattern_search":
        size = 1 << config["shard_bits"]

        def count(ctx, text):
            def fn(patterns, lengths):
                p, ln = np.asarray(patterns), np.asarray(lengths)
                return np.asarray([reference.pattern_count(
                    text, p[i, :ln[i]], within_shard=size)
                    for i in range(len(p))])
            return fn
        return {"count": count}
    raise KeyError(f"no control for traffic kind {kind!r}")


def main(argv=None) -> int:
    from chipbench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench = run.load_bench()
    cell = run.find_cell(bench, args.workload)
    config = run.load_config(cell["config"])
    kind = run.load_traffic(cell["traffic"])["kind"]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(args.workload, seed, args.seconds, False,
                           bench=bench, hooks=hooks(kind, config))
        print("control " + json.dumps({"seed": seed,
                                       "correct": out["correct"],
                                       "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
