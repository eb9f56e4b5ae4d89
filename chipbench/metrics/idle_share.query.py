"""Share of the traced window in which no operation ran on the device:
1 - (union of op intervals) / window, averaged over the chips."""
from chipbench import trace


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = trace.window(run.trace)
    return 100.0 * (1.0 - trace.busy_ns(run.trace, lo, hi) / (hi - lo))
