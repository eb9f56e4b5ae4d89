"""Mean host time of a ``QueryFrontend.pump()`` that served a batch, from
the benchmark's span around each call (probes included)."""


def read(run):
    v = run.result.get("pump_s_mean")
    return None if v is None else 1e3 * v
