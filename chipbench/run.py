"""Run one benchmark cell on the chips of this machine.

    python3 -m chipbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration
(``configs/<config>.json``) and traffic mix (``traffic/<traffic>.json``)
are data files, and the mix's ``kind`` names the driver in ``kinds/``
that generates it, sets the program up, runs the timed window and checks
the answers against ``reference.py``. One run:

1. checks that JAX sees TPUs, at least as many as the cell asks for
   (anything else exits 2 and prints no result);
2. sets up from ``--seed`` (data, structures built or restored from the
   structure cache, warm-up of the cell's own shapes): ``setup_s``;
3. runs the timed window for ``--seconds``; with ``--trace 1`` under the
   profiler, whose trace ``trace.py`` reduces;
4. reads peak device memory, frees the program's state, and compares
   with the reference;
5. prints each compared number beside its limit on standard error, then
   one JSON line on standard output: ``correct``, ``attempted``,
   ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
   ``--trace 1`` its per-layer ones, each read by
   ``metrics/<name>.py``), ``device``, ``breakdown`` (traced runs) and
   last ``checks``.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE = HERE / ".cache"


def log(msg: str) -> None:
    print(msg, flush=True)


# ---- registry: everything is found by name --------------------------------

def load_bench(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(path.read_text())


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def load_traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def load_kind(kind: str):
    return importlib.import_module(f"chipbench.kinds.{kind}")


def load_metric(name: str):
    """``read(run)`` of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str):
    """(end-to-end metrics, per-layer metrics) this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if cell in m["workloads"] or
             ("workloads" not in m and m["moves"] in names)]
    return e2e, layer


# ---- run context ------------------------------------------------------------

class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (a cache hit also fires the compile event, timing the load)."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.compile_s, self.compiles, self.cache_hits

    def since(self, mark) -> dict:
        s, c, h = mark
        return {"compile_s": self.compile_s - s,
                "compiles": self.compiles - c,
                "cache_hits": self.cache_hits - h}


@dataclass
class Ctx:
    """What a ``kinds`` driver gets: the cell's data files, the seed, and
    the benchmark's span recorder. ``hooks`` lets a test put a broken or
    a control path in the program's place; a benchmark run passes none."""
    workload: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    chips: int
    meter: CompileMeter
    cache_dir: Path = CACHE
    hooks: dict = field(default_factory=dict)
    #: benchmark host spans: [(name, start_s, end_s)] on perf_counter
    spans: list = field(default_factory=list)
    tracing: bool = False

    @contextlib.contextmanager
    def span(self, name: str):
        """Time a call into the program under ``chipbench.<name>``; in a
        traced run the span also lands in the profiler's trace."""
        full = f"chipbench.{name}"
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(full)
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.spans.append((full, t0, time.perf_counter()))


@dataclass
class Run:
    """What a per-layer metric reader gets."""
    workload: str
    config: dict
    traffic: dict
    chips: int
    peaks: dict
    result: dict          # the driver's window counters
    spans: list           # Ctx.spans of the window
    trace: object = None  # trace.Trace of the window, or None


def _device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    log(f"device: {json.dumps(info)}")
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"chipbench: no TPU found (JAX platform is "
                         f"{dev.platform!r}); this benchmark measures TPUs "
                         f"only")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell asks for {chips} chips, JAX "
                         f"sees {len(devices)}")
    return info


def _enable_compile_cache() -> str:
    """JAX's persistent cache at ``<checkout>/.jax_cache`` (a fixed path:
    a moved directory never hits), unless JAX_COMPILATION_CACHE_DIR is
    set; every program is cached, however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: dict | None = None, config: dict | None = None,
             traffic: dict | None = None, hooks: dict | None = None,
             require_tpu: bool = True, cache_dir: Path = CACHE,
             start: float | None = None) -> dict:
    """One run of ``workload``; returns the result object (see module
    doc). ``start`` is the ``perf_counter`` reading ``setup_s`` counts
    from (the process's start when run as a program). ``config`` and
    ``traffic`` replace the cell's files, ``hooks`` puts a broken or a
    control path in the program's place (see ``Ctx``), and
    ``require_tpu=False`` skips the look for a chip: all four only for
    the CPU tests of the harness and the controls."""
    start = time.perf_counter() if start is None else start
    bench = bench or load_bench()
    cell = find_cell(bench, workload)
    config = config or load_config(cell["config"])
    traffic = traffic or load_traffic(cell["traffic"])
    chips = int(cell["chips"])
    device = _device_info(chips, require_tpu)
    peaks = None
    if require_tpu:
        from chipbench.peaks import peaks_for
        peaks = peaks_for(device["kind"])
    sys.path.insert(0, str(ROOT / "src"))
    if require_tpu:
        log(f"compile cache: {_enable_compile_cache()}")
    import jax

    ctx = Ctx(workload, config, traffic, seed, seconds, chips, CompileMeter(),
              cache_dir=cache_dir, hooks=hooks or {})
    kind = load_kind(traffic["kind"])
    state = kind.setup(ctx)
    setup_s = time.perf_counter() - start
    log(f"setup: {setup_s:.3f} s in all")

    trace_dir = cache_dir / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.tracing = True
        # device ops and the benchmark's own annotations only: the
        # default Python tracer records every call on the host and slowed
        # the query cell's pumps fivefold
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    mark = ctx.meter.mark()
    ctx.spans.clear()
    with ctx.span("window"):
        res = kind.window(state, seconds, ctx)
    win = ctx.meter.since(mark)
    if trace:
        jax.profiler.stop_trace()
        ctx.tracing = False
    log(f"window: {json.dumps({k: v for k, v in res.items() if k[0] != '_'})}")
    log(f"window compiles: {json.dumps(win)}")
    device["memory_peak_bytes"] = _peak_bytes(chips)

    e2e, layer = cell_metrics(bench, workload)
    values = kind.metrics(state, res)
    run_ctx = Run(workload, config, traffic, chips, peaks, res,
                  list(ctx.spans))
    out_metrics = {}
    breakdown = None
    if trace:
        from chipbench import trace as tr_mod
        run_ctx.trace = tr_mod.load(tr_mod.find_xplane(str(trace_dir)))
        reduced = tr_mod.reduce(run_ctx.trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = reduced["breakdown"]
        for m in layer:
            v = load_metric(m["name"])(run_ctx)
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values["setup_s"] = setup_s
        for m in e2e:
            out_metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    t0 = time.perf_counter()
    attempted, failed, checks = kind.check(state, res, ctx)
    log(f"check: {time.perf_counter() - t0:.3f} s")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": out_metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None, start: float | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), start=start)
    except SystemExit as e:
        print(e, file=sys.stderr, flush=True)
        return 2
    print(json.dumps(out), flush=True)
    return 0
