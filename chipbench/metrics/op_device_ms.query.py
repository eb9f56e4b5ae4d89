"""Device time of the analytics programs per batch served: every program
in the window but the breakers' liveness probe (``_probe_count``), over
the front-end batches that served requests."""
from chipbench import trace


def read(run):
    batches = run.result["batches"]
    if run.trace is None or not run.trace.modules or not batches:
        return None
    lo, hi = trace.window(run.trace)
    ns = trace.program_ns(run.trace, lo, hi, lambda name: "probe" not in name)
    return ns / 1e6 / batches if ns > 0 else None
