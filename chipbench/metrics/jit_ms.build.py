"""Host milliseconds per build in JAX's compile phases: the union of the
``jax.trace``, ``jax.lower`` and ``jax.compile`` spans under the
program's ``store.build`` spans in the window (phases of functions traced
inside another's trace overlap and count once), over the builds."""
from chipbench import program_spans


def read(run):
    found = program_spans.counted(run, "store.build")
    if found is None:
        return None
    spans, builds = found
    jax_spans = [sp for sp in spans if sp.name.startswith("jax.")
                 and sp.path.startswith("store.build/")]
    return 1e3 * program_spans.union_s(jax_spans) / builds
