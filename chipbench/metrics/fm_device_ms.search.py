"""Device time of the count program (``chipbench_fm_count``) per batch."""
from chipbench import trace


def read(run):
    batches = run.result["batches"]
    if run.trace is None or not run.trace.modules or not batches:
        return None
    lo, hi = trace.window(run.trace)
    ns = trace.program_ns(run.trace, lo, hi,
                          lambda name: "chipbench_fm_count" in name)
    return ns / 1e6 / batches if ns > 0 else None
