"""Host milliseconds per build in the program's ``store.build.histogram``
span (the per-shard bincount, the cumsum and the upload of the shard
counts), over the ``store.build`` spans in the window."""
from chipbench import program_spans


def read(run):
    found = program_spans.counted(run, "store.build")
    if found is None:
        return None
    spans, builds = found
    return 1e3 * sum(sp.t1 - sp.t0 for sp in spans
                     if sp.name == "store.build.histogram") / builds
