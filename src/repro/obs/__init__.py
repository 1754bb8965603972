"""Observability: structured metrics, JSONL sinks, span tracing.

The three building blocks (each usable standalone):

* :mod:`repro.obs.metrics` -- thread-safe counters / gauges / fixed-bucket
  histograms in a :class:`~repro.obs.metrics.MetricsRegistry`.
* :mod:`repro.obs.sink`    -- crash-tolerant JSONL artifacts (append +
  fsync-on-flush, size rotation, run-id + monotonic stamping).
* :mod:`repro.obs.tracing` -- nested host-side spans with Chrome
  ``trace_event`` export, each also a ``jax.profiler.TraceAnnotation``,
  and an optional ``jax.profiler.trace`` hook.

:class:`Telemetry` bundles them for the trainer: one registry + tracer per
run, an optional sink when ``ObsConfig.metrics_path`` is set, and a
``close()`` that emits the final metrics snapshot as a ``"summary"`` row
and writes the Chrome trace. Construction is cheap and everything degrades
to near-zero overhead when disabled (null registry, null spans, no sink),
so the trainer always has a telemetry object and never branches on "is
observability on" in the hot path. While ``Telemetry.active()`` is open,
the process's XLA compiles and garbage collections feed that telemetry
(``compile/*``, ``host/gc_*``) and its per-step accumulator
(:meth:`Telemetry.take_step_counts`). Full schema + recipes:
docs/observability.md.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import hashlib
import json
import time

import jax
from jax.profiler import TraceAnnotation

from repro.obs.metrics import (DEFAULT_BYTES_EDGES, DEFAULT_TIME_EDGES_S,
                               MetricsRegistry, NULL_REGISTRY, NullRegistry)
from repro.obs.sink import JsonlSink, new_run_id, read_jsonl, read_run
from repro.obs.tracing import Span, Tracer, jax_profile

__all__ = [
    "DEFAULT_BYTES_EDGES", "DEFAULT_TIME_EDGES_S", "JsonlSink",
    "MetricsRegistry", "NULL_REGISTRY", "NullRegistry", "ObsConfig", "Span",
    "Telemetry", "Tracer", "fingerprint", "jax_profile", "new_run_id",
    "read_jsonl", "read_run",
]


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Trainer-facing observability knobs (``TrainerConfig.obs``)."""

    enabled: bool = True
    #: metrics/event JSONL path; None = in-memory registry only, no artifact
    metrics_path: str | None = None
    #: Chrome trace_event JSON written on close; None = no trace file
    trace_path: str | None = None
    #: jax.profiler.trace log dir wrapped around the run; None = off
    jax_profile_dir: str | None = None
    #: rotate the metrics JSONL when it exceeds this many bytes (0 = never)
    rotate_bytes: int = 0
    #: emit a per-step ``step_phases`` row every N steps (sink only)
    step_metrics_every: int = 1


def fingerprint(obj) -> str:
    """12-hex content hash of a JSON-serializable config summary.

    Deterministic across processes (canonical key order, ``default=str``
    for dtypes and other non-JSON leaves); used to join metrics artifacts
    to the resolved config that produced them (launch/dryrun.py)."""
    blob = json.dumps(obj, sort_keys=True, default=str).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


#: the ``jax.monitoring`` duration event of one XLA backend compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: profiler annotation over one garbage collection
GC_ANNOTATION = "host.gc"

# Telemetry objects inside ``active()``, innermost last. The process-wide
# hooks below are registered once and feed the innermost only, so a
# compile or a collection is counted once, and never by a telemetry that
# is not active (tests build many in one process).
_active: list["Telemetry"] = []
_hooked = False
_gc_open: list = []          # (telemetry, start time, annotation)


def _on_duration(event: str, secs: float, **_) -> None:
    if event == COMPILE_EVENT and _active:
        _active[-1]._observe_compile(secs)


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if _active:
            ann = TraceAnnotation(GC_ANNOTATION)
            ann.__enter__()
            _gc_open.append((_active[-1], time.perf_counter(), ann))
    elif _gc_open:
        tel, t0, ann = _gc_open.pop()
        ann.__exit__(None, None, None)
        tel._observe_gc(time.perf_counter() - t0)


def _hook_once() -> None:
    global _hooked
    if not _hooked:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        gc.callbacks.append(_on_gc)
        _hooked = True


class Telemetry:
    """One run's registry + tracer + (optional) sink, under one run_id."""

    def __init__(self, cfg: ObsConfig | None = None, *,
                 run_id: str | None = None, meta: dict | None = None):
        self.cfg = cfg = cfg or ObsConfig()
        on = cfg.enabled
        self.registry: MetricsRegistry = MetricsRegistry() if on \
            else NULL_REGISTRY
        self.tracer = Tracer(enabled=on, metrics=self.registry)
        self.sink: JsonlSink | None = None
        if on and cfg.metrics_path:
            self.sink = JsonlSink(cfg.metrics_path, run_id=run_id,
                                  rotate_bytes=cfg.rotate_bytes, meta=meta)
        self.run_id = self.sink.run_id if self.sink else \
            (run_id or new_run_id())
        self._closed = False
        self._step_compiles = 0
        self._step_gc_s = 0.0

    @contextlib.contextmanager
    def active(self):
        """While open, the process's XLA backend compiles feed counter
        ``compile/count`` and histogram ``compile/s``, its garbage
        collections feed ``host/gc_collections`` and ``host/gc_s`` (each
        also a ``host.gc`` profiler annotation), and both the per-step
        accumulator. Nested activations feed the innermost telemetry
        only. A disabled telemetry is never fed."""
        if not self.cfg.enabled:
            yield self
            return
        _hook_once()
        _active.append(self)
        try:
            yield self
        finally:
            _active.remove(self)

    def _observe_compile(self, secs: float) -> None:
        self.registry.counter("compile/count").inc()
        self.registry.histogram("compile/s").observe(secs)
        self._step_compiles += 1

    def _observe_gc(self, secs: float) -> None:
        self.registry.counter("host/gc_collections").inc()
        self.registry.histogram("host/gc_s").observe(secs)
        self._step_gc_s += secs

    def take_step_counts(self) -> tuple[int, float]:
        """``(compiles, gc_seconds)`` since the last call, then reset:
        the step loop calls it before and after each step."""
        out = (self._step_compiles, self._step_gc_s)
        self._step_compiles, self._step_gc_s = 0, 0.0
        return out

    def span(self, name: str, step: int | None = None, **args):
        return self.tracer.span(name, step=step, **args)

    def emit(self, record: dict) -> None:
        """Mirror a record to the sink (no-op without one)."""
        if self.sink is not None:
            self.sink.emit(record)

    def event(self, etype: str, **kw) -> dict:
        """Count + emit an event row; returns the (unstamped) record."""
        self.registry.counter(f"events/{etype}").inc()
        rec = {"kind": "event", "event": etype, **kw}
        self.emit(rec)
        return rec

    def flush(self) -> None:
        if self.sink is not None:
            self.sink.flush()

    def summary(self) -> dict:
        """The final snapshot row (also what ``close`` emits)."""
        return {"kind": "summary", "run_id": self.run_id,
                "metrics": self.registry.snapshot()}

    def close(self) -> None:
        """Emit the summary row, export the Chrome trace, close the sink.
        Idempotent; safe to call on a run that crashed mid-step."""
        if self._closed:
            return
        self._closed = True
        if self.sink is not None:
            self.sink.emit(self.summary())
            self.sink.close()
        if self.cfg.enabled and self.cfg.trace_path:
            self.tracer.export_chrome_trace(self.cfg.trace_path)

    def __enter__(self) -> "Telemetry":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
