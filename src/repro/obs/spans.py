"""In-program spans on the host clock that the device trace maps onto.

``span(name, rid=None, **attrs)`` marks one piece of the served path (an
engine run, a restoration op, a decode step and its per-token steps, ...).
When recording is on it does two things:

  * opens a ``jax.profiler.TraceAnnotation`` named ``cacheflow/<name>``, so
    a profile shows the span beside the device ops it launched;
  * appends ``(name, t0_ns, t1_ns, parent_index, rid, attrs)`` to one
    process-wide log.  Times are ``time.perf_counter_ns()``;
    ``parent_index`` is the log index of the innermost span open when this
    one started (None at the top).

Recording is on while the ``CACHEFLOW_TELEMETRY`` opt-in is set (read once,
at import) or while a profiler session collects
(``TraceAnnotation.is_enabled()``).  Off, ``span`` returns one shared null
context: no clock read, nothing appended.  The log keeps at most
:data:`MAX_SPANS` spans and counts what it drops past that.

Compiles are spans too: one ``jax.monitoring`` time-span listener folds
JAX's three compile stages (trace, lowering, backend compile) of each
compiled function into one ``compile`` span, child of the span that was
open when the compile ran, carrying ``fun_name`` and the seconds of each
stage.  JAX stamps those stages with ``time.time()``; one anchor pair,
taken when the listener is registered, moves them onto the span clock.

Every span name is declared in ``obs/registry.py``'s ``SPAN_CATALOG``
(checked by ``analysis/codelint.py``).
"""
from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

import jax

#: most spans the log holds; later spans are dropped and counted
MAX_SPANS = 1 << 20

#: JAX's compile-stage events -> the stage's key in a compile span's attrs
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_LAST_STAGE = "backend_s"

_is_profiling = jax.profiler.TraceAnnotation.is_enabled


def telemetry_env_enabled() -> bool:
    """The ``CACHEFLOW_TELEMETRY`` opt-in, same convention as the
    sanitizer's ``CACHEFLOW_SANITIZE``."""
    return os.environ.get(
        "CACHEFLOW_TELEMETRY", "0").lower() not in ("", "0", "false")


def _union_s(intervals) -> float:
    """Seconds covered by the union of ``(t0_ns, t1_ns)`` intervals."""
    total, end = 0, None
    for t0, t1 in sorted(intervals):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total * 1e-9


class SpanLog:
    """The recorded spans of one process.  A record is a list
    ``[name, t0_ns, t1_ns, parent_index, rid, attrs]``; an open span has
    ``t1_ns`` None."""

    def __init__(self, cap: int = MAX_SPANS):
        self.cap = cap
        self.records: List[list] = []
        self.dropped = 0
        self._open: List[int] = []        # indices of the open spans
        self._stages: List[tuple] = []    # compile stages awaiting backend
        self._anchor: Optional[tuple] = None   # (time_ns, perf_counter_ns)

    def clear(self) -> None:
        self.records.clear()
        self.dropped = 0
        self._open.clear()
        self._stages.clear()

    def parent(self) -> Optional[int]:
        return self._open[-1] if self._open else None

    def open(self, name: str, rid, attrs: dict) -> Optional[int]:
        if len(self.records) >= self.cap:
            self.dropped += 1
            return None
        i = len(self.records)
        self.records.append([name, time.perf_counter_ns(), None,
                             self.parent(), rid, attrs])
        self._open.append(i)
        return i

    def close(self, i: int) -> None:
        self.records[i][2] = time.perf_counter_ns()
        if self._open[-1] == i:
            self._open.pop()
        else:
            self._open.remove(i)

    def instant(self, name: str, rid, attrs: dict) -> None:
        if len(self.records) >= self.cap:
            self.dropped += 1
            return
        t = time.perf_counter_ns()
        self.records.append([name, t, t, self.parent(), rid, attrs])

    def mark(self) -> int:
        """Index from which a caller's spans start: the outermost open
        span, or the next record when none is open."""
        return self._open[0] if self._open else len(self.records)

    # -- compiles ------------------------------------------------------------
    def listen_compiles(self) -> None:
        """Register the compile listener once per log, taking the anchor
        pair that maps ``time.time()`` onto ``perf_counter_ns``."""
        if self._anchor is not None:
            return
        self._anchor = (time.time_ns(), time.perf_counter_ns())
        jax.monitoring.register_event_time_span_listener(self._on_stage)

    def _to_span_clock(self, wall_s: float) -> int:
        wall0, perf0 = self._anchor
        return perf0 + int(round(wall_s * 1e9)) - wall0

    def _on_stage(self, event: str, start_s: float, end_s: float,
                  **kw) -> None:
        stage = COMPILE_STAGES.get(event)
        if stage is None or not recording():
            return
        parent = self.parent()
        self._stages.append((stage, self._to_span_clock(start_s),
                             self._to_span_clock(end_s), parent))
        if stage != _LAST_STAGE:
            return
        # one compiled function: the stages that ran under the same open
        # span since the last backend compile (a trace whose lowering was
        # cached, under an earlier span, is not this compile's)
        mine = [s for s in self._stages if s[3] == parent]
        self._stages.clear()
        attrs: Dict[str, object] = {"fun_name": str(kw.get("fun_name", "?"))}
        for key in COMPILE_STAGES.values():
            attrs[key] = _union_s([(a, b) for k, a, b, _ in mine if k == key])
        if len(self.records) >= self.cap:
            self.dropped += 1
            return
        self.records.append(["compile", min(s[1] for s in mine),
                             max(s[2] for s in mine), parent, None, attrs])


_LOG = SpanLog()
_forced = False


def force_recording(on: bool) -> bool:
    """Force recording on or off (profiler sessions aside); returns the
    previous setting.  The ``CACHEFLOW_TELEMETRY`` opt-in sets it at
    import."""
    global _forced
    prev, _forced = _forced, bool(on)
    if _forced:
        _LOG.listen_compiles()
    return prev


def recording() -> bool:
    return _forced or _is_profiling()


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "rid", "attrs", "_ann", "_i")

    def __init__(self, name: str, rid, attrs: dict):
        self.name, self.rid, self.attrs = name, rid, attrs

    def __enter__(self):
        _LOG.listen_compiles()
        self._ann = jax.profiler.TraceAnnotation("cacheflow/" + self.name)
        self._ann.__enter__()
        self._i = _LOG.open(self.name, self.rid, self.attrs)
        return self

    def __exit__(self, *exc):
        if self._i is not None:
            _LOG.close(self._i)
        self._ann.__exit__(*exc)
        return False

    def set(self, **attrs) -> None:
        """Attributes known only once the span's work is done."""
        self.attrs.update(attrs)


def span(name: str, rid=None, **attrs):
    """Context manager over one piece of the served path (see module
    docstring); ``.set(**attrs)`` on it adds attributes before it ends."""
    if not recording():
        return _NULL
    return _Span(name, rid, attrs)


def instant(name: str, rid=None, **attrs) -> None:
    """A zero-length span: one host-clock stamp under the open span."""
    if recording():
        _LOG.instant(name, rid, attrs)


def now_ns() -> int:
    """The span clock."""
    return time.perf_counter_ns()


def log() -> SpanLog:
    return _LOG


def export(lo: int = 0, hi: Optional[int] = None) -> List[dict]:
    """Records ``[lo, hi)`` as plain JSON, parent indices relative to
    ``lo`` (None where the parent lies outside the slice)."""
    out = []
    for name, t0, t1, parent, rid, attrs in _LOG.records[lo:hi]:
        out.append({"name": name, "t0_ns": t0, "t1_ns": t1,
                    "parent": (parent - lo if parent is not None
                               and parent >= lo else None),
                    "rid": rid, "attrs": dict(attrs)})
    return out


force_recording(telemetry_env_enabled())
