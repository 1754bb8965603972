"""Nested span tracing with Chrome ``trace_event`` export.

The ROADMAP's top open item asks to measure *actual* comm/compute overlap
on a real backward pass instead of only auditing the bucketed schedule
statically from HLO (``launch/hlo_stats.bucket_audit``). Host-side spans
are the first half of that instrument: the trainer wraps each step's
phases (``data`` / ``dispatch`` / ``sync_wait`` / ``checkpoint``) in
``with tracer.span(...)``, giving a per-step wall-time breakdown that the
metrics JSONL records and :meth:`Tracer.export_chrome_trace` renders as a
Chrome/Perfetto-loadable ``trace_event`` file. The second half is the
device timeline: :func:`jax_profile` wraps the run in
``jax.profiler.trace`` so the XLA trace (where the per-bucket all-reduces
are visible overlapping backward compute) can be captured alongside.
docs/observability.md walks the full overlap-measurement recipe.

Spans are exception-safe (the record is closed and flagged ``error`` when
the body raises) and nest per-thread: depth/parent come from a
thread-local stack, timestamps from the monotonic clock relative to the
tracer's epoch -- wall-clock-free, like the sink stamps (repro.obs.sink).
An enabled tracer also opens a ``jax.profiler.TraceAnnotation`` named
``ANNOTATION_PREFIX + name`` for each span's lifetime, so a profiler
session records the phases on the device trace's clock (``trainer.data``
and so on); without a session an annotation costs well under a
microsecond. The tracer keeps the newest ``MAX_SPANS`` closed spans and
counts the older ones it drops in ``trace/dropped_spans``.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

from repro.obs.metrics import NULL_REGISTRY

#: profiler annotation name of a span: ``trainer.step``, ``trainer.data``...
ANNOTATION_PREFIX = "trainer."
#: closed spans kept (a 90-epoch one-chip run closes about 2.7M)
MAX_SPANS = 65_536


class Span:
    """One closed (or in-flight) span. ``duration`` is None until exit."""

    __slots__ = ("name", "t0", "duration", "depth", "parent", "tid", "step",
                 "args", "error")

    def __init__(self, name: str, t0: float, depth: int, parent: str | None,
                 tid: int, step: int | None, args: dict):
        self.name = name
        self.t0 = t0
        self.duration: float | None = None
        self.depth = depth
        self.parent = parent
        self.tid = tid
        self.step = step
        self.args = args
        self.error = False

    @property
    def t1(self) -> float:
        return self.t0 + (self.duration or 0.0)

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, t0={self.t0:.6f}, "
                f"dur={self.duration}, depth={self.depth})")


_NULL_SPAN = Span("null", 0.0, 0, None, 0, None, {})
_NULL_SPAN.duration = 0.0


class Tracer:
    """Collects closed spans; thread-safe, nesting tracked per thread."""

    def __init__(self, enabled: bool = True, metrics=NULL_REGISTRY):
        self.enabled = enabled
        self._t0 = time.monotonic()
        self._lock = threading.Lock()
        self._closed: collections.deque[Span] = collections.deque(
            maxlen=MAX_SPANS)
        self._dropped = metrics.counter("trace/dropped_spans")
        self._local = threading.local()

    def _stack(self) -> list[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, step: int | None = None, **args):
        """``with tracer.span("sync/bucket3", step=7) as sp:`` -- on exit
        ``sp.duration`` holds the elapsed seconds; a profiler session
        records the body as ``trainer.sync/bucket3``. Yields a shared null
        span, and opens no annotation, when the tracer is disabled."""
        if not self.enabled:
            yield _NULL_SPAN
            return
        stack = self._stack()
        sp = Span(name, time.monotonic() - self._t0, depth=len(stack),
                  parent=stack[-1].name if stack else None,
                  tid=threading.get_ident(), step=step, args=args)
        stack.append(sp)
        try:
            with TraceAnnotation(ANNOTATION_PREFIX + name):
                yield sp
        except BaseException:
            sp.error = True
            raise
        finally:
            sp.duration = time.monotonic() - self._t0 - sp.t0
            stack.pop()
            with self._lock:
                if len(self._closed) == self._closed.maxlen:
                    self._dropped.inc()
                self._closed.append(sp)

    def spans(self, name: str | None = None,
              step: int | None = None) -> list[Span]:
        """Closed spans, optionally filtered, ordered by start time."""
        with self._lock:
            out = list(self._closed)
        if name is not None:
            out = [s for s in out if s.name == name]
        if step is not None:
            out = [s for s in out if s.step == step]
        out.sort(key=lambda s: s.t0)
        return out

    def export_chrome_trace(self, path: str) -> int:
        """Write closed spans as Chrome ``trace_event`` JSON (complete
        "X" events, microsecond timestamps); load via chrome://tracing or
        https://ui.perfetto.dev. Returns the number of events written."""
        with self._lock:
            closed = sorted(self._closed, key=lambda s: (s.t0, s.depth))
        tids: dict[int, int] = {}
        events = []
        for sp in closed:
            tid = tids.setdefault(sp.tid, len(tids))
            args = {k: v for k, v in sp.args.items()}
            if sp.step is not None:
                args["step"] = sp.step
            if sp.error:
                args["error"] = True
            events.append({
                "name": sp.name, "cat": "host", "ph": "X",
                "ts": round(sp.t0 * 1e6, 3),
                "dur": round((sp.duration or 0.0) * 1e6, 3),
                "pid": 0, "tid": tid,
                "args": args,
            })
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return len(events)


@contextlib.contextmanager
def jax_profile(log_dir: str | None):
    """Optionally wrap a block in ``jax.profiler.trace(log_dir)``.

    ``log_dir=None`` (the default everywhere) is a no-op; otherwise the
    XLA device trace (TensorBoard / Perfetto format) lands in ``log_dir``,
    which is how bucketed-overlap claims are checked against the *device*
    timeline rather than host wall time (docs/observability.md)."""
    if not log_dir:
        yield
        return
    import jax
    with jax.profiler.trace(log_dir):
        yield
