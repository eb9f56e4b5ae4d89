"""Share of the traced window in which the first chip is idle and no
program span is open on the host: the idle time the program's spans do
not name."""
from chipbench import program_spans


def read(run):
    return program_spans.idle_share(run, lambda sp: True, named=False)
