"""Mean lateness of the load generator: submission time minus due time."""


def read(run):
    return 1e3 * run.result["late_s_mean"] if run.result["requests"] else None
