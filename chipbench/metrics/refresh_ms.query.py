"""Host milliseconds per front-end pump in the breakers' refresh: the
program's ``frontend.breakers.refresh`` spans in the window over its
``frontend.pump`` spans there."""
from chipbench import program_spans


def read(run):
    found = program_spans.counted(run, "frontend.pump")
    if found is None:
        return None
    spans, pumps = found
    return 1e3 * sum(sp.t1 - sp.t0 for sp in spans
                     if sp.name == "frontend.breakers.refresh") / pumps
