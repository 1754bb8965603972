"""Device idle time under the trainer's host phases.

The trainer opens a profiler annotation ``trainer.<phase>`` around each
phase of a step (``data``, ``dispatch``, ``sync_wait``, ``log``,
``checkpoint``), on the device trace's clock. A device gap that falls under
``trainer.data`` is the chip waiting for the host to make the next batch;
one under ``trainer.sync_wait`` falls while the host already waits on the
step, so the chip waits on something else (a peer chip, a transfer).

The window opens inside the first traced step's data phase, after the
trainer entered its annotation, so the profiler never records that one
``trainer.data``; the harness's ``bench.data`` annotation around the
``data_fn`` call inside it is recorded, and counts as data phase too.
"""

from __future__ import annotations

from bench import trace

PREFIX = "trainer."
# harness annotations that lie inside a trainer phase
INSIDE = {"data": ("bench.data",)}


def idle_ms(ctx, sample, phase):
    """Device idle ms per window step under the host's ``trainer.<phase>``
    annotation, averaged over the devices. None where the cell's sample
    differs, there is no trace, or the trace holds no such annotation."""
    if (ctx.sample != sample or ctx.trace is None or ctx.steps == 0
            or not ctx.trace.devices):
        return None
    host = ctx.trace.host
    if not any(name == PREFIX + phase for name, _, _ in host):
        return None
    names = (PREFIX + phase,) + INSIDE.get(phase, ())
    under = trace.union((s, e) for name, s, e in host if name in names)
    devices = ctx.trace.devices
    idle = sum(trace.length(d.gaps) - trace.length(trace.minus(d.gaps, under))
               for d in devices) / len(devices)
    return 1e3 * idle / ctx.steps
