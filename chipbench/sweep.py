"""Find the knee of an open-loop query cell: one set-up, then one window
per offered rate, each with its own schedule.

    python3 -m chipbench.sweep --config pile_vocab17 --traffic audit \\
        --rates 10,20,40 --seconds 15 --seed 1

The configuration and the traffic mix are found by name, as a cell's
are, so a mix can be swept before a cell of it is declared.

Prints one JSON line per rate: requests, exact / degraded / shed /
missing answers, the p50 and p95 from due time, batches served, mean
pump time, the generator's lateness and the mean queue depth seen at
submission in each quarter of the window (a depth that climbs from
quarter to quarter is a growing queue). Rates are tried in the order
given, up to the first that sheds, degrades or loses a request. The
knee is the highest rate
with nothing shed or degraded and no growing queue; the cell's
``rate_per_s`` is set at about 0.8 of it.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from chipbench import run
from chipbench.kinds import range_audit


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="pile_vocab17")
    ap.add_argument("--traffic", default="audit")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    config, traffic = run.load_config(args.config), run.load_traffic(
        args.traffic)
    run._device_info(1, True)
    sys.path.insert(0, str(run.ROOT / "src"))
    run._enable_compile_cache()
    ctx = run.Ctx(f"{args.config}.{args.traffic}", config, traffic,
                  args.seed, args.seconds, 1, run.CompileMeter())
    st = range_audit.setup(ctx)
    n, sigma = len(st.toks), config["sigma"]
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        st.tr = dict(traffic, rate_per_s=rate)
        st.req = range_audit.schedule(args.seed + i, st.tr, n, sigma,
                                      args.seconds)
        mark = ctx.meter.mark()
        res = range_audit.window(st, args.seconds, ctx)
        lat = res["_latency"]
        ok = lat[np.isfinite(lat)]
        print("sweep " + json.dumps({
            "rate_per_s": rate,
            **{k: v for k, v in res.items() if not k.startswith("_")},
            "p50_ms": float(np.median(ok)) * 1e3 if len(ok) else None,
            "p95_ms": range_audit.p95_ms(lat, float("nan")),
            "compiles": ctx.meter.since(mark)}), flush=True)
        if res["shed"] or res["degraded"] or res["missing"]:
            break                       # past the knee: stop climbing
    st.fe.breakers.close_pool()
    return 0


if __name__ == "__main__":
    sys.exit(main())
