"""Nested trace spans: host-side timing that lines up with device profiles.

``span("restore.verify", shards=8)`` times a block, nests (a thread-local
stack gives every span a ``/``-joined path), records the duration into the
``span.<name>`` histogram, keeps the finished span in a bounded in-memory
record (``finished_spans()``, the last 2^16), appends a structured event
to the JSONL event log when an exporter is configured, and forwards the
name to ``jax.profiler.TraceAnnotation`` so the same block shows up in
device profiles under the same label. No ``jax.named_scope``: a span open
while a function is traced leaves the compiled program as it would be
without it.

JAX's own compile work becomes child spans of the calling thread's open
span: one listener on ``jax.monitoring``'s time spans, registered at the
first span, turns tracing to a jaxpr, lowering to MLIR and the backend
compile into ``jax.trace``, ``jax.lower`` and ``jax.compile`` (attribute
``fun``; a compile served from the persistent cache, whose lookup lies
inside ``jax.compile``, has ``cache_hit`` set and counts as
``jax.compiles{cache=hit}``, others as ``{cache=miss}``). Only the
outermost phases are kept: a function traced inside another's trace
(every ``jnp`` call in a jitted builder) lies inside its caller's
``jax.trace``, so a build holds a handful of phase spans, not hundreds.
Compile work with no span open is not recorded.

Async dispatch makes naive host timing lie: a jitted call returns before
the device finishes. ``sp.sync(out)`` registers the call's output, and the
span blocks on it (``jax.block_until_ready``) at exit *before* reading the
clock — opt-in, because blocking inside a pipelined serving loop would
serialize it.

When metrics are disabled the context manager yields a shared no-op span
and touches nothing, and the JAX listener returns at once.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
import uuid

from . import export as _export
from .metrics import _state, counter, histogram

_tls = threading.local()
#: finished spans, oldest first; bounded so a long-lived process holds
#: the last 2^16 and no more
_FINISHED: collections.deque = collections.deque(maxlen=1 << 16)

#: jax.monitoring time-span events turned into child spans
JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_jax_listening = False
_jax_lock = threading.Lock()


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class Span:
    """One span: ``t0``/``t1`` on ``time.perf_counter``, ``ts`` the wall
    clock (Unix seconds) at ``t0``; ``t1`` and ``dur_s`` are set when it
    finishes."""
    __slots__ = ("name", "path", "attrs", "t0", "t1", "ts", "dur_s",
                 "span_id", "parent_id", "_sync")

    def __init__(self, name: str, path: str, attrs: dict,
                 parent_id: str | None):
        self.name = name
        self.path = path
        self.attrs = attrs
        self.span_id = uuid.uuid4().hex[:12]
        self.parent_id = parent_id
        self.ts = time.time()
        self.t0 = time.perf_counter()
        self.t1 = None
        self.dur_s = None
        self._sync = None

    def set(self, key: str, value) -> None:
        """Attach an attribute discovered mid-span (exported at exit)."""
        self.attrs[key] = value

    def sync(self, value):
        """Register device work to block on at span exit; returns it."""
        self._sync = value
        return value


class _NullSpan:
    """Disabled-mode stand-in: every method is a no-op."""
    __slots__ = ()

    def set(self, key, value):
        pass

    def sync(self, value):
        return value


_NULL = _NullSpan()


def current_span() -> Span | None:
    st = _stack()
    return st[-1] if st else None


def finished_spans() -> list:
    """The finished spans still held (the last 2^16), oldest first."""
    return list(_FINISHED)


def clear_spans() -> None:
    """Forget every finished span."""
    _FINISHED.clear()


def _finish(sp: Span) -> None:
    sp.dur_s = sp.t1 - sp.t0
    histogram("span." + sp.name).observe(sp.dur_s)
    _FINISHED.append(sp)
    _export.emit_event("span", sp.name, ts=sp.ts, dur_s=sp.dur_s,
                       path=sp.path, span_id=sp.span_id,
                       parent_id=sp.parent_id, attrs=sp.attrs or None)


def event(name: str, kind: str = "event", **attrs) -> None:
    """Emit a structured event correlated to the currently open span (the
    fault-injection hook: a fault fired inside a chaos scenario's span
    shows up inside that span's subtree)."""
    if not _state.enabled:
        return
    sp = current_span()
    _export.emit_event(kind, name,
                       span_id=sp.span_id if sp is not None else None,
                       attrs=attrs or None)


# ---- JAX compile phases as child spans ------------------------------------

def _on_jax_event(event: str, **_) -> None:
    if _state.enabled and event == _CACHE_HIT:
        _tls.cache_hit = True          # fires inside the backend compile


def _on_jax_phase_start(event: str, start_time: float, **_) -> None:
    if _state.enabled and event in JAX_PHASES:
        _tls.jax_depth = getattr(_tls, "jax_depth", 0) + 1


def _on_jax_time_span(event: str, start_time: float, end_time: float,
                      **kwargs) -> None:
    name = JAX_PHASES.get(event)
    if name is None:
        return
    # the phase bookkeeping runs whether or not obs is on, so a phase that
    # straddles a switch leaves no stale depth or cache hit behind
    depth = getattr(_tls, "jax_depth", 0)
    if depth:
        _tls.jax_depth = depth - 1
    hit = False
    if name == "jax.compile" and getattr(_tls, "cache_hit", False):
        hit, _tls.cache_hit = True, False
    parent = current_span()
    if not _state.enabled or parent is None or depth > 1:
        return                         # depth > 1: a phase inside a phase
    attrs = {"fun": kwargs.get("fun_name")}
    if name == "jax.compile":
        attrs["cache_hit"] = hit
        counter("jax.compiles", cache="hit" if hit else "miss").inc()
    to_perf = time.perf_counter() - time.time()
    sp = Span(name, f"{parent.path}/{name}", attrs, parent.span_id)
    sp.ts, sp.t0, sp.t1 = start_time, start_time + to_perf, end_time + to_perf
    _finish(sp)


def _listen_to_jax(jax) -> None:
    global _jax_listening
    with _jax_lock:
        if not _jax_listening:
            jax.monitoring.register_event_listener(_on_jax_event)
            jax.monitoring.register_scalar_listener(_on_jax_phase_start)
            jax.monitoring.register_event_time_span_listener(
                _on_jax_time_span)
            _jax_listening = True


@contextlib.contextmanager
def span(name: str, **attrs):
    """Context manager timing a nested, attributed span (see module doc)."""
    if not _state.enabled:
        yield _NULL
        return
    import jax
    if not _jax_listening:
        _listen_to_jax(jax)
    st = _stack()
    parent = st[-1] if st else None
    path = f"{parent.path}/{name}" if parent else name
    sp = Span(name, path, dict(attrs),
              parent.span_id if parent else None)
    st.append(sp)
    try:
        with jax.profiler.TraceAnnotation(name):
            yield sp
    finally:
        if sp._sync is not None:
            try:
                jax.block_until_ready(sp._sync)
            except Exception:                                 # noqa: BLE001
                pass    # a failed computation still ends the span
            sp._sync = None     # the record must not hold device buffers
        sp.t1 = time.perf_counter()
        st.pop()
        _finish(sp)
