"""Share of the traced window in which the first chip is idle while one of
the program's JAX compile-phase spans (``jax.trace``, ``jax.lower``,
``jax.compile``) is open on the host."""
from chipbench import program_spans


def read(run):
    return program_spans.idle_share(
        run, lambda sp: sp.name.startswith("jax."))
