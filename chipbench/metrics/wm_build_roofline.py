"""Share of the HBM roofline that the construction reaches: the bytes any
builder must move for the window's chunks, from n and sigma alone (the
input at 4 B/token read once, n * ceil(log2 sigma) / 8 B of level
bitmaps written once), over the device-busy time of the window (every
device op there is the build's), over the chips' peak HBM bandwidth."""
import math

from chipbench import trace


def read(run):
    if run.trace is None or not run.trace.ops or not run.result["tokens"]:
        return None
    lo, hi = trace.window(run.trace)
    busy_s = trace.busy_ns(run.trace, lo, hi) / 1e9
    if busy_s <= 0:
        return None
    nbits = math.ceil(math.log2(run.config["sigma"]))
    need = run.result["tokens"] * (4 + nbits / 8)
    peak = run.peaks["hbm_bytes_per_s"] * run.chips
    return 100.0 * need / busy_s / peak
