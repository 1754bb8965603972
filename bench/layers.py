"""Shared arithmetic of the metric readers in ``bench/metrics``. Each
reader returns None where its cell gives it nothing to read."""

from __future__ import annotations


def rate(ctx, sample):
    """Samples completed over the whole window, per second."""
    if ctx.sample != sample or ctx.steps == 0:
        return None
    return ctx.steps * ctx.samples_per_step / ctx.window_s


def mfu(ctx, sample):
    """Model FLOPs of the traced steps over traced window x chips x the
    chip's bf16 peak, in percent."""
    if ctx.sample != sample or ctx.trace is None or ctx.steps == 0:
        return None
    return 100.0 * ctx.flops_per_step * ctx.steps / (
        ctx.trace.window_s * ctx.chips * ctx.peak["bf16_flops_per_s"])


def idle_share(ctx, sample):
    """1 - busy / window per device, averaged over the devices, percent."""
    if ctx.sample != sample or ctx.trace is None or not ctx.trace.devices:
        return None
    d = ctx.trace.devices
    return 100.0 * sum(1.0 - x.busy_s / ctx.trace.window_s for x in d) / len(d)


def host_ms(ctx, sample):
    """Mean per window step of the trainer's data, dispatch, log and
    checkpoint spans (its host work besides waiting for the step)."""
    if ctx.sample != sample or ctx.steps == 0:
        return None
    tot = sum(sum(ctx.spans.get(k, ())) for k in
              ("data", "dispatch", "log", "checkpoint"))
    return 1e3 * tot / ctx.steps


def step_device_ms(ctx, sample):
    """Device time of the train-step program per step, on the busiest
    device."""
    if ctx.sample != sample or ctx.trace is None or ctx.steps == 0:
        return None
    per = [sum(v for k, v in d.programs.items() if k == ctx.step_program)
           for d in ctx.trace.devices]
    if not per or max(per) == 0:
        return None
    return 1e3 * max(per) / ctx.steps


def input_device_ms(ctx, sample):
    """Device time per step of every program other than the train step,
    summed over the devices."""
    if ctx.sample != sample or ctx.trace is None or ctx.steps == 0:
        return None
    tot = sum(v for d in ctx.trace.devices for k, v in d.programs.items()
              if k != ctx.step_program)
    return 1e3 * tot / ctx.steps


def exchange_ms(ctx, exposed):
    """Collective device time per step (or its part with no other op
    running), averaged over the devices; None on one chip."""
    if ctx.trace is None or ctx.chips < 2 or ctx.steps == 0:
        return None
    d = ctx.trace.devices
    key = "collective_exposed_s" if exposed else "collective_s"
    return 1e3 * sum(getattr(x, key) for x in d) / len(d) / ctx.steps
