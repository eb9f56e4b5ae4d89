"""Device-level profiling: HLO cost-model stats, roofline utilization,
device-memory gauges, and opt-in ``jax.profiler`` trace capture.

This is the layer that relates a *measured* op time to the *hardware
ceiling* — the accounting Fischer–Kurpicz (1702.07578) and Labeit et al.
(1407.8142) win by (memory traffic per level), generalized from the old
``benchmarks/roofline.py`` / ``launch/hlo_analysis.py`` pair into a
reusable ``repro.obs`` facility every instrumented op shares.

Three ingredients:

* **Cost model** — ``compiled_cost``/``compiled_memory`` read XLA's
  ``cost_analysis()`` (FLOPs, bytes accessed) and ``memory_analysis()``
  (argument/output/temp bytes → peak working set) off an AOT-compiled
  executable. ``analyze_hlo`` (moved here from ``launch/hlo_analysis``)
  re-derives dot FLOPs and collective bytes from the post-SPMD HLO text
  with ``known_trip_count`` multipliers, because ``cost_analysis`` counts
  while-loop bodies ONCE (a scan-over-levels program under-reports by
  ~num_levels×).
* **Roofline gauges** — ``profile_op``/``profiled_op`` compile, read the
  cost model, time steady-state, and record the ``prof.*{op=...}`` gauge
  family: flops, bytes_accessed, peak_bytes, arithmetic intensity,
  achieved FLOP/s and B/s, and ``prof.roofline_util`` = (cost-model bound
  time) / (measured time) — 1.0 means the op runs as fast as the hardware
  model allows, ≪1 means there is headroom the kernels are leaving on the
  table. The hardware model is deliberately coarse (published peaks,
  env-overridable) — utilization is a *trend*
  metric for the regression sentry, not a certificate. Peaks are keyed by
  the device's ``device_kind``; a kind without a published entry raises.
* **Memory gauges** — ``record_memory_gauges`` snapshots
  ``jax.live_arrays()`` (count + bytes actually held alive) and, where
  the backend exposes it, ``device.memory_stats()`` peak/in-use bytes.

Opt-in device tracing: every serving CLI takes ``--profile-dir``;
``start_trace``/``stop_trace`` (or the ``trace`` context manager) wrap
the serving section in ``jax.profiler`` capture so the spans recorded by
``obs.span`` line up with the device timeline.

Zero repro-internal imports (jax is imported lazily inside functions), so
any layer can profile itself without cycles.
"""
from __future__ import annotations

import contextlib
import os
import re
from typing import Dict, List, Optional, Tuple

from .metrics import counter, gauge
from .timing import Stopwatch, time_compiled, track_shapes

# ---------------------------------------------------------------------------
# hardware model
# ---------------------------------------------------------------------------

#: (peak FLOP/s, HBM bandwidth B/s) of one device, keyed by the
#: ``device_kind`` JAX reports for it:
#:
#: * ``"TPU v5 lite"`` (TPU v5e) — Google Cloud documentation, "TPU v5e":
#:   197 TFLOP/s bf16, 819 GB/s HBM.
#: * ``"cpu"`` — an order-of-magnitude estimate for development runs (a
#:   few AVX cores + DDR), not a published peak. A roofline share computed
#:   against it is never a device metric.
#:
#: A kind missing here is an error, not a default: add it with its source.
#: ``REPRO_PEAK_FLOPS`` / ``REPRO_HBM_BW`` override the looked-up values.
PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197e12, 819e9),
    "cpu": (2.0e11, 5.0e10),
}

#: ICI link bandwidth (B/s/link) for the collective term of the dryrun
#: roofline (TPU v5e-class).
LINK_BW = 50e9


def hw_model(device_kind: str | None = None) -> Tuple[float, float]:
    """(peak FLOP/s, HBM B/s) for ``device_kind`` (default: the kind of
    ``jax.devices()[0]``), with ``REPRO_PEAK_FLOPS`` / ``REPRO_HBM_BW`` env
    overrides. Raises ``KeyError`` for a kind ``PEAKS`` does not list."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to repro.obs.prof.PEAKS "
                       f"with its source")
    peak, bw = PEAKS[device_kind]
    peak = float(os.environ.get("REPRO_PEAK_FLOPS", peak))
    bw = float(os.environ.get("REPRO_HBM_BW", bw))
    return peak, bw


# ---------------------------------------------------------------------------
# compiled-executable cost/memory stats
# ---------------------------------------------------------------------------

def compiled_cost(compiled) -> Dict[str, float]:
    """FLOPs / bytes-accessed from ``compiled.cost_analysis()`` (a flat
    dict; {} when the backend exposes no cost model). NOTE: while-loop
    bodies are counted once — for loop-heavy programs prefer
    ``analyze_hlo`` on ``compiled.as_text()``.
    """
    ca = compiled.cost_analysis() or {}
    out = {}
    for key, name in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed"),
                      ("transcendentals", "transcendentals")):
        v = ca.get(key)
        if v is not None:
            out[name] = float(v)
    return out


def compiled_memory(compiled) -> Dict[str, float]:
    """Argument/output/temp/code bytes from ``compiled.memory_analysis()``
    plus ``peak_bytes`` (the executable's device working set: arguments +
    outputs + temporaries − aliased)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:                                         # noqa: BLE001
        return {}
    if ma is None:
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr.replace("_size_in_bytes", "_bytes")] = float(v)
    if out:
        out["peak_bytes"] = (out.get("argument_bytes", 0.0)
                             + out.get("output_bytes", 0.0)
                             + out.get("temp_bytes", 0.0)
                             - out.get("alias_bytes", 0.0))
    return out


def live_memory_stats() -> Dict[str, float]:
    """Live device memory: count/bytes of arrays currently held alive
    (``jax.live_arrays``) and, when the backend reports allocator stats
    (TPU/GPU), in-use and peak bytes."""
    import jax
    arrs = jax.live_arrays()
    total = 0
    for a in arrs:
        try:
            total += int(a.size) * a.dtype.itemsize
        except Exception:                                     # noqa: BLE001
            continue
    stats: Dict[str, float] = {"live_arrays": float(len(arrs)),
                               "live_bytes": float(total)}
    try:
        ms = jax.devices()[0].memory_stats()
    except Exception:                                         # noqa: BLE001
        ms = None
    if ms:
        if ms.get("bytes_in_use") is not None:
            stats["device_bytes_in_use"] = float(ms["bytes_in_use"])
        if ms.get("peak_bytes_in_use") is not None:
            stats["device_peak_bytes"] = float(ms["peak_bytes_in_use"])
    return stats


def record_memory_gauges() -> Dict[str, float]:
    """Snapshot ``live_memory_stats`` into the ``prof.mem.*`` gauges."""
    stats = live_memory_stats()
    for k, v in stats.items():
        gauge("prof.mem." + k).set(v)
    return stats


# ---------------------------------------------------------------------------
# roofline profiling of one op
# ---------------------------------------------------------------------------

def _record_cost_gauges(op: str, compiled, steady_s: float,
                        work_elements: Optional[float] = None) -> dict:
    """Read the cost model off ``compiled`` and record the ``prof.*``
    gauge family for ``op``; returns the stats as a dict."""
    cost = compiled_cost(compiled)
    mem = compiled_memory(compiled)
    peak_flops, hbm_bw = hw_model()
    flops = cost.get("flops", 0.0)
    nbytes = cost.get("bytes_accessed", 0.0)
    stats: dict = {"op": op, "steady_s": steady_s, **cost}
    if mem:
        stats["peak_bytes"] = mem["peak_bytes"]
        gauge("prof.peak_bytes", op=op).set(mem["peak_bytes"])
    if flops:
        gauge("prof.flops", op=op).set(flops)
    if nbytes:
        gauge("prof.bytes_accessed", op=op).set(nbytes)
    if flops and nbytes:
        stats["ai"] = flops / nbytes
        gauge("prof.ai", op=op).set(stats["ai"])
    t_compute = flops / peak_flops
    t_memory = nbytes / hbm_bw
    roofline_s = max(t_compute, t_memory)
    stats["compute_s"] = t_compute
    stats["memory_s"] = t_memory
    if steady_s > 0:
        if flops:
            stats["achieved_flops_s"] = flops / steady_s
            gauge("prof.achieved_flops_s", op=op).set(flops / steady_s)
        if nbytes:
            stats["achieved_bytes_s"] = nbytes / steady_s
            gauge("prof.achieved_bytes_s", op=op).set(nbytes / steady_s)
        if work_elements:
            stats["melem_per_s"] = work_elements / steady_s / 1e6
            gauge("prof.melem_per_s", op=op).set(stats["melem_per_s"])
        if roofline_s > 0:
            # fraction of the hardware ceiling achieved: bound-time /
            # measured-time. 1.0 = at the roofline; ≪1 = headroom.
            stats["roofline_util"] = roofline_s / steady_s
            stats["bound"] = ("compute" if t_compute >= t_memory
                              else "memory")
            gauge("prof.roofline_util", op=op).set(stats["roofline_util"])
            counter("prof.bound", op=op, term=stats["bound"]).inc()
    gauge("prof.steady_s", op=op).set(steady_s)
    return stats


def _aot(fn, *args):
    """AOT lower+compile ``fn`` (jitting it first when needed)."""
    import jax
    jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
    return jfn.lower(*args).compile()


def profile_op(name: str, fn, *args, iters: int = 1,
               work_elements: Optional[float] = None):
    """Compile ``fn(*args)`` ahead-of-time, read its HLO cost model, time
    steady-state executions, and record the ``prof.*{op=name}`` roofline
    gauge family (+ the ``prof.mem.*`` device-memory gauges).

    Returns ``(out, stats)`` — ``stats`` holds flops / bytes / peak_bytes
    / roofline_util / achieved rates (whatever the backend exposes).
    ``work_elements`` (e.g. sequence length, query count) additionally
    derives ``prof.melem_per_s``. A failed compile or run raises: an op
    that cannot be profiled cannot be served either.
    """
    sw = Stopwatch()
    compiled = _aot(fn, *args)
    compile_s = sw.lap()
    out, steady_s, _ = time_compiled(compiled, *args, iters=iters)
    stats = _record_cost_gauges(name, compiled, steady_s,
                                work_elements=work_elements)
    stats["compile_s"] = compile_s
    record_memory_gauges()
    return out, stats


def profiled_op(layer: str, op: str, fn, *args, batch: int = 1,
                iters: int = 1):
    """``obs.timed_op`` + roofline profiling in one AOT compile.

    Emits the standard ``serve.<layer>.<op>.*`` metric family (latency
    histogram, compile_s/batch/qps gauges, calls counter, shape tracking)
    AND the ``prof.*{op=<layer>.<op>}`` cost-model gauges, compiling only
    once. Returns ``(out, steady_s, compile_s)`` — drop-in for
    ``timed_op``.
    """
    name = f"{layer}.{op}"
    prefix = f"serve.{name}"
    sw = Stopwatch()
    compiled = _aot(fn, *args)
    compile_s = sw.lap()
    out, steady_s, _ = time_compiled(compiled, *args, iters=iters)
    track_shapes(name, *args)
    counter(prefix + ".calls").inc(1 + max(1, iters))
    from .metrics import histogram
    histogram(prefix + ".latency_s").observe(steady_s)
    gauge(prefix + ".compile_s").set(compile_s)
    gauge(prefix + ".batch").set(batch)
    if steady_s > 0:
        gauge(prefix + ".qps").set(batch / steady_s)
    _record_cost_gauges(name, compiled, steady_s, work_elements=batch)
    record_memory_gauges()
    return out, steady_s, compile_s


# ---------------------------------------------------------------------------
# opt-in jax.profiler trace capture (--profile-dir on the serving CLIs)
# ---------------------------------------------------------------------------

_trace_active = False


def start_trace(profile_dir) -> bool:
    """Start a ``jax.profiler`` trace into ``profile_dir`` (no-op and
    False on a falsy dir or if a trace is already running)."""
    global _trace_active
    if not profile_dir or _trace_active:
        return False
    import jax
    jax.profiler.start_trace(str(profile_dir))
    _trace_active = True
    return True


def stop_trace() -> bool:
    """Stop the running trace (no-op and False when none is active)."""
    global _trace_active
    if not _trace_active:
        return False
    import jax
    try:
        jax.profiler.stop_trace()
    finally:
        _trace_active = False
    return True


@contextlib.contextmanager
def trace(profile_dir):
    """Context manager form of start/stop_trace; no-op on a falsy dir."""
    started = start_trace(profile_dir)
    try:
        yield
    finally:
        if started:
            stop_trace()


# ---------------------------------------------------------------------------
# post-SPMD HLO analysis (absorbed from launch/hlo_analysis)
# ---------------------------------------------------------------------------
# XLA's cost_analysis() counts while-loop bodies ONCE, which under-reports
# any scan-over-layers program by ~num_layers×. analyze_hlo re-derives dot
# FLOPs and collective bytes from compiled.as_text(): it builds the
# computation call graph (while bodies weighted by their backend_config
# known_trip_count), walks every computation with its execution
# multiplier, prices dots as 2·numel(result)·contraction (operand shapes
# resolved through a per-computation symbol table) and collectives as
# result-shape bytes.

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
                "bf16": 2, "f16": 2, "u16": 2, "s16": 2,
                "f32": 4, "s32": 4, "u32": 4,
                "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
# computation headers are the only non-indented "%name (" lines (params may
# contain nested tuple parens, so only anchor on the name)
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s+\(")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _numel(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _first_shape(sig: str) -> Tuple[str, str]:
    m = _SHAPE_RE.search(sig)
    return (m.group(1), m.group(2)) if m else ("f32", "")


def parse_computations(hlo: str) -> Dict[str, List[str]]:
    """computation name -> list of instruction lines."""
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in hlo.splitlines():
        hdr = _COMP_HDR_RE.match(line)
        if hdr and "{" in line:
            cur = hdr.group(1)
            comps[cur] = []
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None and "=" in line:
            comps[cur].append(line)
    return comps


def _entry_name(hlo: str) -> str:
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.M)
    return m.group(1) if m else next(iter(parse_computations(hlo)))


def analyze_hlo(hlo: str) -> Dict:
    """Per-device dot FLOPs and collective bytes from post-SPMD HLO text
    (see the section comment above for why cost_analysis is not enough)."""
    comps = parse_computations(hlo)
    entry = _entry_name(hlo)

    # ---- per-computation: symbol table + edges + local costs ------------
    sym: Dict[str, Dict[str, Tuple[str, str]]] = {}
    edges: Dict[str, List[Tuple[str, int]]] = {}
    local_flops: Dict[str, float] = {}
    local_coll: Dict[str, Dict[str, int]] = {}

    for cname, lines in comps.items():
        table: Dict[str, Tuple[str, str]] = {}
        cedges: List[Tuple[str, int]] = []
        flops = 0.0
        coll: Dict[str, int] = {}
        for line in lines:
            mi = _INSTR_RE.match(line)
            if not mi:
                continue
            iname, rest = mi.groups()
            dt, dims = _first_shape(rest)
            table[iname] = (dt, dims)
            # ---- call edges ----
            if " while(" in rest:
                mb = re.search(r"body=%?([\w.\-]+)", rest)
                trip = 1
                mt = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', rest)
                if mt:
                    trip = int(mt.group(1))
                if mb:
                    cedges.append((mb.group(1), trip))
                mc = re.search(r"condition=%?([\w.\-]+)", rest)
                if mc:
                    cedges.append((mc.group(1), trip))
            for mcall in re.finditer(
                    r"(?:calls=|to_apply=)%?([\w.\-]+)", rest):
                cedges.append((mcall.group(1), 1))
            for mbr in re.finditer(
                    r"(?:true_computation=|false_computation=|branch_computations=\{)"
                    r"%?([\w.\-]+)", rest):
                cedges.append((mbr.group(1), 1))
            # ---- collectives ----
            # XLA:CPU's FloatSupport promotes bf16 all-reduces to f32
            # (reducer named "*promoted"); TPU all-reduces bf16 natively,
            # so promoted ops are counted at their true 2-byte width.

            def _cbytes():
                b = _numel(dims) * _DTYPE_BYTES.get(dt, 4)
                if dt == "f32" and "promoted" in rest:
                    b //= 2
                return b

            for kind in _COLLECTIVES:
                if f" {kind}(" in rest or rest.startswith(f"{kind}("):
                    if f"{kind}-start" in rest or f"{kind}-done" in rest:
                        continue
                    coll[kind] = coll.get(kind, 0) + _cbytes()
                    break
            for kind in _COLLECTIVES:
                if f" {kind}-start(" in rest:
                    coll[kind] = coll.get(kind, 0) + _cbytes()
                    break
            # ---- dot flops ----
            if " dot(" in rest:
                ops = re.findall(r"%([\w.\-]+)", rest)
                lhs = ops[0] if ops else None
                mcd = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rest)
                csize = 1
                if lhs and lhs in table and mcd:
                    ldims = table[lhs][1].split(",")
                    for ci in mcd.group(1).split(","):
                        if ci and int(ci) < len(ldims) and ldims[int(ci)]:
                            csize *= int(ldims[int(ci)])
                flops += 2.0 * _numel(dims) * csize
        sym[cname] = table
        edges[cname] = cedges
        local_flops[cname] = flops
        local_coll[cname] = coll

    # ---- propagate multipliers from entry -------------------------------
    mult: Dict[str, float] = {}

    def visit(name: str, m: float):
        mult[name] = mult.get(name, 0.0) + m
        for child, trip in edges.get(name, ()):  # conditions counted too
            visit(child, m * trip)

    visit(entry, 1.0)

    total_flops = sum(local_flops.get(c, 0.0) * m for c, m in mult.items())
    total_coll: Dict[str, float] = {}
    for c, m in mult.items():
        for kind, b in local_coll.get(c, {}).items():
            total_coll[kind] = total_coll.get(kind, 0.0) + b * m
    return {"dot_flops_per_device": total_flops,
            "collective_bytes_per_device": total_coll,
            "num_computations": len(comps)}
