"""Chip benchmark of the succinct-structure engine (see ``run.py``).

Everything the benchmark measures with lives here and is found by name
from ``BENCHMARK.json``: configurations in ``configs/<name>.json``,
traffic mixes in ``traffic/<name>.json`` (each names the ``kinds/``
driver that generates it), per-layer metric readers in
``metrics/<name>.py``. The program under test is reached only through its
public entry points under ``src/repro``.
"""
