"""The distributed trainer: the paper's full recipe wired together,
hardened for faults (docs/robustness.md).

One ``train_step`` =
    shard_map over the data-parallel axes (model axis stays XLA-auto):
      1. local forward/backward in compute dtype (bf16; paper: fp16),
         loss multiplied by the dynamic loss scale
      2. gradient exchange with the configured strategy
         (2D-torus / ring / hierarchical / psum), bf16 buckets, fp32 for BN;
         ``TrainerConfig.grad_sync.bucket_bytes > 0`` splits the exchange
         into size-targeted buckets issued in reverse-backprop order so XLA
         overlaps each bucket with remaining backward compute
         (docs/gradient_sync.md)
      3. non-finite guard: an all-finite flag over the pmean'd loss and
         every synced gradient leaf gates the update -- params and momentum
         pass through unchanged on a non-finite step and the loss scale
         backs off (recovering after ``GuardConfig.growth_interval`` clean
         steps)
      4. LR + momentum from the schedule at the *fractional epoch*
      5. LARS update in fp32

The ``Trainer`` loops over the batch-size-control stages (paper §2.1) with
ONE step function (jit re-specializes per stage batch shape), retries
transient data failures with jittered exponential backoff
(``repro.utils.retry``), writes crash-consistent checkpoints periodically
and at stage boundaries -- by default *asynchronously*, off the training
thread (``checkpoint.AsyncCheckpointWriter``) -- resumes mid-stage from
the newest *valid* checkpoint, and degrades the grad-sync strategy
(torus2d -> ring -> psum) instead of aborting when the configured one
cannot run on the current mesh (or a torus axis is down).

``run`` itself is a **supervised recovery loop** (``repro.train.elastic``,
docs/robustness.md "Elastic recovery"): when the supervisor flags a
*permanent* failure mid-run -- a torus axis newly down, an unbroken streak
of guard-skipped steps, repeated step timeouts -- the trainer re-resolves
the sync strategy against the enlarged down-axis set, rebuilds the jitted
step for the degraded mesh, restores the newest valid checkpoint, and
re-enters the step loop in the same process; only an exhausted recovery
budget (or recovery without any checkpoint) aborts. Faults, including the
permanent classes, are injectable via ``repro.testing.chaos.FaultPlan``
for chaos testing.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
import statistics
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.core import grad_sync as grad_sync_lib
from repro.core import lars as lars_lib
from repro.core import schedules as sched_lib
from repro.core.batch_control import TrainPlan, epoch_of
from repro.core.grad_sync import GradSyncConfig, sync_tree
from repro.core.topology import TorusGrid, select_grid
from repro.obs import ObsConfig, Telemetry
from repro.obs.metrics import NULL_REGISTRY
from repro.obs.tracing import jax_profile
from repro.testing.chaos import RETRYABLE
from repro.train import checkpoint
from repro.train.elastic import ElasticConfig, PermanentFailure, Supervisor
from repro.train.state import TrainState
from repro.utils.retry import retry_call

# stalled-step report: a step whose wall exceeds STALL_FACTOR x the median
# of the attempt's previous (at most STALL_WINDOW) steps, once there are
# STALL_MIN_STEPS of them, emits a ``step_stall`` event
STALL_FACTOR = 2.0
STALL_WINDOW = 16
STALL_MIN_STEPS = 4


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Non-finite-gradient guard + dynamic loss scale (paper trains in
    reduced precision; this is the standard overflow guard).

    Defaults are bf16-friendly (scale 1.0 -- bf16 shares fp32's exponent
    range, so scaling only matters after a fault); an fp16 run would start
    at ``init_scale=2**15``. With ``init_scale=1.0`` and no faults the
    guarded step is bit-identical to an unguarded one (multiply by exactly
    1.0, select-on-True), so enabling the guard costs no reproducibility.
    """

    enabled: bool = True
    init_scale: float = 1.0
    growth_interval: int = 200    # clean steps before the scale regrows
    growth_factor: float = 2.0
    backoff_factor: float = 0.5   # applied on every skipped step
    max_scale: float = 2.0 ** 15
    min_scale: float = 1.0


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    schedule: str = "B"                 # LR config A or B (paper Table 3)
    label_smoothing: float = 0.1
    grad_sync: GradSyncConfig = GradSyncConfig()
    lars: lars_lib.LARSConfig = lars_lib.LARSConfig()
    guard: GuardConfig = GuardConfig()
    aux_weight: float = 0.01            # MoE load-balance weight
    log_every: int = 10
    # fault tolerance (docs/robustness.md)
    ckpt_every_steps: int = 0           # 0: stage boundaries only
    ckpt_keep_last: int = 3
    ckpt_retries: int = 3
    ckpt_async: bool = True             # commit off the training thread
    ckpt_max_pending: int = 2           # async writer queue bound
    data_retries: int = 3
    retry_backoff_s: float = 0.05       # base of the exponential backoff
    elastic: ElasticConfig = ElasticConfig()  # mid-run recovery supervisor
    # observability (docs/observability.md): metrics JSONL / Chrome trace /
    # jax profiler paths; registry + tracer always run (near-zero cost)
    obs: ObsConfig = ObsConfig()


def make_train_step(loss_fn: Callable, mesh, dp_axes: tuple[str, ...],
                    cfg: TrainerConfig, grid: TorusGrid | None = None,
                    donate: bool = True):
    """Build the jitted step.

    ``loss_fn(params, batch, dp_axes) -> (loss, aux)`` computes the LOCAL
    (per-shard) mean loss; ``batch`` is the local shard inside shard_map.
    ``aux`` is an extra scalar loss term already locally averaged.

    The returned fn is batch-shape-polymorphic: jit re-specializes per
    stage shape, so ONE call to this builder serves every stage of a
    batch-size-control plan.
    """
    grid = grid or select_grid(dp_axes)
    schedule = sched_lib.make(cfg.schedule)
    guard = cfg.guard

    def step(state: TrainState, batch, epoch, global_batch):
        scale = state.loss_scale

        def total_loss(p):
            with jax.named_scope("forward"):
                loss, aux = loss_fn(p, batch, dp_axes)
            tot = loss + cfg.aux_weight * aux
            if guard.enabled:
                tot = tot * scale.astype(tot.dtype)
            return tot, (loss, aux)

        (_, (loss, aux)), grads = jax.value_and_grad(
            total_loss, has_aux=True)(state.params)
        with jax.named_scope("exchange"):
            grads = sync_tree(grads, grid, cfg.grad_sync)
        with jax.named_scope("guard"):
            if guard.enabled:
                inv = 1.0 / scale   # exact for the power-of-two scales
                grads = jax.tree.map(lambda g: g * inv.astype(g.dtype),
                                     grads)
            loss_m = jax.lax.pmean(loss, dp_axes)
            # all-finite flag over loss + synced grads: the all-reduce
            # already propagated any shard's NaN/Inf to every shard, so the
            # flag (and the skip decision) is identical across the mesh.
            nonfinite = sum(
                jnp.sum(~jnp.isfinite(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads))
            finite = jnp.isfinite(loss_m) & (nonfinite == 0)

        lr = schedule.lr(epoch)
        mom = schedule.mom(epoch, global_batch)
        with jax.named_scope("lars"):
            new_params, new_opt = lars_lib.update(
                state.params, grads, state.opt_state, lr=lr, momentum=mom,
                cfg=cfg.lars)

        if guard.enabled:
            with jax.named_scope("guard"):
                # skip the update on non-finite steps: params/momentum pass
                # through unchanged (jnp.where selects bit-exactly on True)
                sel = functools.partial(jnp.where, finite)
                new_params = jax.tree.map(sel, new_params, state.params)
                new_opt = jax.tree.map(sel, new_opt, state.opt_state)
                good = jnp.where(finite, state.good_steps + 1, 0)
                grow = finite & (good >= guard.growth_interval)
                new_scale = jnp.where(
                    finite,
                    jnp.where(grow,
                              jnp.minimum(scale * guard.growth_factor,
                                          guard.max_scale),
                              scale),
                    jnp.maximum(scale * guard.backoff_factor,
                                guard.min_scale))
                good = jnp.where(grow, 0, good).astype(jnp.int32)
        else:
            new_scale, good = state.loss_scale, state.good_steps

        metrics = {
            "loss": loss_m,
            "aux": jax.lax.pmean(aux, dp_axes),
            "lr": lr, "momentum": mom,
            "grad_norm": jnp.sqrt(sum(
                jnp.sum(g.astype(jnp.float32) ** 2)
                for g in jax.tree.leaves(grads))),
            "skipped": (~finite).astype(jnp.int32),
            "nonfinite_count": nonfinite.astype(jnp.int32),
            "loss_scale": new_scale,
        }
        new_state = TrainState(new_params, new_opt, state.step + 1,
                               new_scale, good)
        return new_state, metrics

    # shard_map: manual over DP axes, auto over whatever else (model axis)
    manual = set(dp_axes)
    batch_spec = P(dp_axes)
    smapped = shard_map(
        step, mesh=mesh,
        in_specs=(P(), batch_spec, P(), P()),
        out_specs=(P(), P()),
        axis_names=frozenset(manual), check_vma=False)
    return jax.jit(smapped, donate_argnums=(0,) if donate else ())


def batch_layout(batch, rows: NamedSharding) -> tuple[int, bool]:
    """``(devices, resharded)`` of a batch, from host metadata alone: the
    number of devices its first leaf lives on (0 for a host array), and
    whether any leaf is not laid out as ``rows``, the step's split of the
    batch, so that the step's dispatch must move it first."""
    leaves = jax.tree.leaves(batch)
    shardings = [getattr(x, "sharding", None) for x in leaves]
    devices = 0 if shardings[0] is None else len(shardings[0].device_set)
    resharded = any(s is None or not s.is_equivalent_to(rows, x.ndim)
                    for s, x in zip(shardings, leaves))
    return devices, resharded


@dataclasses.dataclass
class Trainer:
    mesh: Any
    dp_axes: tuple[str, ...]
    loss_fn: Callable
    cfg: TrainerConfig
    plan: TrainPlan
    data_fn: Callable                  # (step_index, global_batch) -> batch
    checkpoint_dir: str | None = None
    fault_plan: Any | None = None      # repro.testing.chaos.FaultPlan
    telemetry: Any | None = None       # repro.obs.Telemetry; None: built
                                       # from cfg.obs and closed by run()

    def run(self, state: TrainState, max_steps: int | None = None,
            log: Callable = print, resume: bool = False):
        """Run the plan under elastic supervision. Returns
        ``(state, history)``.

        ``history`` holds per-step metric rows (every ``log_every`` steps,
        at stage ends, and on every skipped step) interleaved with event
        rows (grad-sync downgrades, data retries, checkpoint
        saves/recoveries, resume, ``elastic_failure`` /
        ``elastic_recovery``, ``step_stall``). Every row carries a ``"kind"`` marker --
        ``"metric"`` or ``"event"`` -- so a serialized history round-trips
        through JSONL unambiguously; rows are mirrored to the run's
        telemetry sink (``cfg.obs.metrics_path``) with per-step phase
        breakdowns and a final metrics summary (docs/observability.md).
        ``resume=True`` restores the newest *valid* checkpoint from
        ``checkpoint_dir`` and fast-forwards the plan to the exact
        mid-stage step.

        On a :class:`~repro.train.elastic.PermanentFailure` the loop
        re-resolves the sync strategy against the accumulated down axes,
        rebuilds the step fn, rolls back to the newest valid checkpoint,
        and continues in-process; after a recovery, step rows for the
        replayed span appear twice in ``history`` (pre- and post-rollback).
        """
        history: list[dict] = []
        cfg = self.cfg
        tel = self.telemetry
        own_tel = tel is None
        if own_tel:
            # one telemetry bundle per run; closed (summary row + trace
            # export) in the finally below. A caller-supplied telemetry is
            # left open -- the caller owns its lifecycle and run_id.
            tel = Telemetry(cfg.obs, meta={
                "source": "trainer", "schedule": cfg.schedule,
                "strategy": cfg.grad_sync.strategy,
                "bucket_bytes": cfg.grad_sync.bucket_bytes})

        def event(etype: str, **kw):
            history.append(tel.event(etype, **kw))
            log(f"[{etype}] " + " ".join(f"{k}={v}" for k, v in kw.items()))

        grid = select_grid(self.dp_axes)
        if self.fault_plan is None:
            initial_down: tuple[str, ...] = ()
        elif hasattr(self.fault_plan, "down_axes_at"):
            initial_down = tuple(self.fault_plan.down_axes_at(0))
        else:
            initial_down = tuple(getattr(self.fault_plan, "down_axes", ())
                                 or ())
        supervisor = Supervisor(cfg.elastic, initial_down_axes=initial_down,
                                metrics=tel.registry)

        writer = None
        if self.checkpoint_dir and cfg.ckpt_async:
            writer = checkpoint.AsyncCheckpointWriter(
                max_pending=cfg.ckpt_max_pending, retries=cfg.ckpt_retries,
                backoff_s=cfg.retry_backoff_s, metrics=tel.registry)

        data_fn = (self.fault_plan.wrap_data_fn(self.data_fn)
                   if self.fault_plan is not None else self.data_fn)

        try:
            start_step = 0
            if resume and self.checkpoint_dir:
                path = checkpoint.latest_valid(
                    self.checkpoint_dir, like=state,
                    on_skip=lambda p, reason: event(
                        "checkpoint_rejected", path=os.path.basename(p),
                        reason=reason))
                if path is not None:
                    state = checkpoint.restore(path, state)
                    start_step = int(state.step)
                    event("resume", path=os.path.basename(path),
                          step=start_step)

            # elastic recovery line: a permanent failure heals by rolling
            # back to a checkpoint, so commit one before the first
            # (buffer-donating) step consumes the initial state
            if (cfg.elastic.enabled and self.checkpoint_dir
                    and checkpoint.latest(self.checkpoint_dir) is None):
                self._save_checkpoint(state, None, event, writer,
                                      metrics=tel.registry)

            # -- supervised recovery loop (docs/robustness.md); optionally
            # under jax.profiler.trace so the device timeline (per-bucket
            # all-reduces overlapping backward) is captured alongside the
            # host spans (docs/observability.md)
            with jax_profile(cfg.obs.jax_profile_dir
                             if cfg.obs.enabled else None), tel.active():
                while True:
                    context = ("startup" if supervisor.recoveries == 0
                               else "elastic")
                    # params_like: lets bucket_bytes="auto" tune against
                    # the real gradient structure (and re-tune for the
                    # degraded strategy after an elastic downgrade)
                    sync_cfg, sync_events = \
                        grad_sync_lib.resolve_sync_config(
                            cfg.grad_sync, grid, self.mesh, self.dp_axes,
                            down_axes=supervisor.down_axes, context=context,
                            params_like=state.params)
                    for ev in sync_events:
                        ev = dict(ev)
                        event(ev.pop("event"), **ev)
                    run_cfg = dataclasses.replace(cfg, grad_sync=sync_cfg)
                    # the bucket schedule is a host-side function of the
                    # param structure + resolved config: publish it as
                    # per-bucket gauges (re-published after a downgrade)
                    grad_sync_lib.record_bucket_metrics(
                        state.params, run_cfg.grad_sync, tel.registry)
                    # ONE step fn for every stage of this attempt: jit
                    # re-specializes per batch shape. (A per-global-batch
                    # cache here would store identical fns -- the builder
                    # never sees the batch size -- while hiding the
                    # per-stage recompile behind a dict hit.)
                    fn = make_train_step(self.loss_fn, self.mesh,
                                         self.dp_axes, run_cfg, grid=grid)
                    # place the state as the step returns it (replicated
                    # on the mesh): a fresh or restored host state would
                    # otherwise compile the first step a second time
                    state = jax.device_put(
                        state, NamedSharding(self.mesh, P()))
                    try:
                        state = self._run_steps(
                            fn, state, run_cfg, data_fn, start_step,
                            max_steps, supervisor, writer, history, event,
                            log, tel)
                        return state, history
                    except PermanentFailure as failure:
                        state, start_step = self._recover(
                            state, failure, supervisor, writer, event)
        finally:
            if writer is not None:
                writer.close()
                self._drain(writer, event)
            if own_tel:
                tel.close()

    # -- the per-attempt step loop ----------------------------------------

    def _run_steps(self, fn, state: TrainState, cfg: TrainerConfig, data_fn,
                   start_step: int, max_steps: int | None,
                   supervisor: Supervisor, writer, history: list, event,
                   log, tel) -> TrainState:
        """One supervised attempt over the plan; raises
        :class:`PermanentFailure` when the supervisor flags one.

        Each step runs inside a ``step`` span with ``data`` / ``dispatch`` /
        ``sync_wait`` / ``log`` / ``checkpoint`` children covering its full
        body, so the phase durations account for (nearly all of) the step's
        wall time -- docs/observability.md asserts the sum lands within 10%.
        A step much slower than the attempt's recent ones emits a
        ``step_stall`` event with its phases, compiles and GC time.
        """
        reg = tel.registry
        walls = collections.deque(maxlen=STALL_WINDOW)
        rows = NamedSharding(self.mesh, P(self.dp_axes))
        for stage in self.plan.stages:
            gb = stage.global_batch
            if start_step >= stage.first_step + stage.num_steps:
                continue       # fast-forward: stage fully covered by ckpt
            for i in range(stage.num_steps):
                gstep = stage.first_step + i
                if gstep < start_step:
                    continue   # fast-forward to the exact mid-stage step
                if max_steps is not None and gstep >= max_steps:
                    return state
                # pre-step health probe: a collective launched over a dead
                # axis wedges the mesh, so detection must win that race
                failure = supervisor.check_health(gstep, self.fault_plan)
                if failure is not None:
                    raise failure
                epoch = epoch_of(self.plan, stage, i)
                tel.take_step_counts()
                with tel.span("step", step=gstep) as sp_step:
                    with tel.span("data", step=gstep) as sp_data:
                        batch = self._fetch_batch(data_fn, gstep, gb, event)
                        if self.fault_plan is not None:
                            batch = self.fault_plan.corrupt_batch(gstep,
                                                                  batch)
                    t0 = time.monotonic()
                    with tel.span("dispatch", step=gstep) as sp_disp:
                        state, metrics = fn(state, batch,
                                            jnp.asarray(epoch, jnp.float32),
                                            jnp.asarray(gb, jnp.float32))
                    done = gstep + 1
                    # reading the flag forces a host sync; without the guard
                    # there is nothing to read and dispatch stays async
                    # (then elapsed_s covers dispatch only -- wall-clock
                    # timeout detection needs the guard's sync or injected
                    # signals)
                    with tel.span("sync_wait", step=gstep) as sp_sync:
                        skipped = (int(metrics["skipped"])
                                   if cfg.guard.enabled else 0)
                    elapsed = time.monotonic() - t0
                    timed_out = (
                        self.fault_plan is not None
                        and hasattr(self.fault_plan, "step_timed_out")
                        and self.fault_plan.step_timed_out(gstep))
                    with tel.span("log", step=gstep) as sp_log:
                        if (done % cfg.log_every == 0
                                or i == stage.num_steps - 1 or skipped):
                            m = {k: float(v) for k, v in metrics.items()}
                            m.update(
                                step=done, epoch=epoch, global_batch=gb,
                                skipped=skipped,
                                nonfinite_count=int(
                                    metrics["nonfinite_count"]),
                                kind="metric")
                            history.append(m)
                            tel.emit(m)
                            log(f"step {done:5d} epoch {epoch:6.2f} "
                                f"gb {gb:6d} loss {m['loss']:.4f} "
                                f"lr {m['lr']:.3f} mom {m['momentum']:.3f}"
                                + (f" SKIPPED "
                                   f"(nonfinite={m['nonfinite_count']}, "
                                   f"scale->{m['loss_scale']:g})"
                                   if skipped else ""))
                    # detection strictly precedes the periodic save: a
                    # failure here must not first persist a checkpoint whose
                    # step counter has advanced past the streak's skipped
                    # updates
                    failure = supervisor.observe_step(
                        gstep, skipped=bool(skipped), timed_out=timed_out,
                        elapsed_s=elapsed)
                    if failure is not None:
                        raise failure
                    with tel.span("checkpoint", step=gstep) as sp_ckpt:
                        if (self.checkpoint_dir and cfg.ckpt_every_steps
                                and done % cfg.ckpt_every_steps == 0
                                and supervisor.healthy):
                            self._save_checkpoint(state, stage, event,
                                                  writer,
                                                  metrics=tel.registry)
                        if writer is not None:
                            self._drain(writer, event)
                # host-side step accounting (outside the step span so the
                # recording cost is not inside what it measures)
                compiles, gc_s = tel.take_step_counts()
                phases = {"data": sp_data.duration,
                          "dispatch": sp_disp.duration,
                          "sync_wait": sp_sync.duration,
                          "log": sp_log.duration,
                          "checkpoint": sp_ckpt.duration}
                wall = sp_step.duration
                if len(walls) >= STALL_MIN_STEPS:
                    median = statistics.median(walls)
                    if wall > STALL_FACTOR * median:
                        reg.counter("step/stalls").inc()
                        event("step_stall", step=done,
                              wall_s=round(wall, 6),
                              median_s=round(median, 6),
                              phases={k: round(v, 6)
                                      for k, v in phases.items()},
                              compiles=compiles, gc_s=round(gc_s, 6))
                walls.append(wall)
                reg.histogram("step/wall_s").observe(wall)
                reg.histogram("step/data_s").observe(sp_data.duration)
                reg.histogram("step/sync_wait_s").observe(sp_sync.duration)
                reg.counter("train/steps").inc()
                devices, resharded = batch_layout(batch, rows)
                reg.gauge("data/batch_devices").set(devices)
                if resharded:
                    reg.counter("data/resharded_batches").inc()
                if cfg.guard.enabled:
                    if skipped:
                        reg.counter("train/skipped_steps").inc()
                        reg.counter("train/nonfinite_total").inc(
                            int(metrics["nonfinite_count"]))
                    reg.gauge("train/loss_scale").set(
                        float(metrics["loss_scale"]))
                if (tel.sink is not None
                        and done % max(1, cfg.obs.step_metrics_every) == 0):
                    tel.emit({
                        "kind": "metric", "metric": "step_phases",
                        "step": done, "wall_s": wall, "phases": phases})
            # stage-boundary save, unless the periodic save just covered it
            if self.checkpoint_dir and not (
                    cfg.ckpt_every_steps
                    and int(state.step) % cfg.ckpt_every_steps == 0):
                with tel.span("checkpoint", step=int(state.step)):
                    self._save_checkpoint(state, stage, event, writer,
                                          metrics=tel.registry)
        return state

    # -- recovery paths ---------------------------------------------------

    def _recover(self, state: TrainState, failure: PermanentFailure,
                 supervisor: Supervisor, writer, event
                 ) -> tuple[TrainState, int]:
        """Roll back past a permanent failure: flush in-flight saves, fold
        the failure into supervisor state, restore the newest valid
        checkpoint. Returns ``(state, start_step)`` for the next attempt;
        raises ``RuntimeError`` when recovery is impossible."""
        event("elastic_failure", kind=failure.kind, step=failure.step,
              down_axes=list(failure.down_axes), detail=failure.detail)
        if supervisor.exhausted:
            raise RuntimeError(
                f"elastic recovery budget exhausted "
                f"({supervisor.cfg.max_recoveries} recoveries) at step "
                f"{failure.step}: {failure.kind}") from failure
        if writer is not None:
            # durability barrier: every enqueued save must be committed (or
            # failed) before latest_valid decides where to roll back to
            writer.flush()
            self._drain(writer, event)
        attempt = supervisor.start_recovery(failure)
        path = None
        if self.checkpoint_dir:
            path = checkpoint.latest_valid(
                self.checkpoint_dir, like=state,
                on_skip=lambda p, reason: event(
                    "checkpoint_rejected", path=os.path.basename(p),
                    reason=reason))
        if path is None:
            raise RuntimeError(
                f"permanent failure at step {failure.step} "
                f"({failure.kind}) but no valid checkpoint to roll back "
                "to -- set checkpoint_dir to enable elastic recovery"
            ) from failure
        state = retry_call(
            lambda: checkpoint.restore(path, state),
            retries=self.cfg.ckpt_retries,
            backoff_s=self.cfg.retry_backoff_s, retry_on=(OSError,),
            seed=failure.step)
        start_step = int(state.step)
        event("elastic_recovery", attempt=attempt, step=start_step,
              path=os.path.basename(path),
              down_axes=list(supervisor.down_axes))
        return state, start_step

    def _fetch_batch(self, data_fn, gstep: int, gb: int, event):
        """Fetch with the shared jittered-backoff retry helper."""
        try:
            return retry_call(
                lambda: data_fn(gstep, gb),
                retries=self.cfg.data_retries,
                backoff_s=self.cfg.retry_backoff_s, retry_on=RETRYABLE,
                on_retry=lambda attempt, e: event(
                    "data_retry", step=gstep, attempt=attempt,
                    error=f"{type(e).__name__}: {e}"),
                seed=gstep)
        except RETRYABLE as e:
            raise RuntimeError(
                f"data_fn failed at step {gstep} after "
                f"{self.cfg.data_retries + 1} attempts") from e

    def _save_checkpoint(self, state: TrainState, stage, event,
                         writer=None, metrics=NULL_REGISTRY) -> None:
        """Crash-consistent save; a checkpoint failure is an event, not a
        training abort (the run continues from the previous checkpoint).
        With ``writer`` the commit runs off-thread (its own ``metrics``
        registry, given at construction) and its outcome events arrive via
        :meth:`_drain`."""
        hook = (self.fault_plan.checkpoint_io_hook
                if self.fault_plan is not None else None)
        meta = ({"stage_end_epoch": stage.stage.end_epoch,
                 "global_batch": stage.global_batch}
                if stage is not None else {"initial": True})
        if writer is not None:
            try:
                writer.save(self.checkpoint_dir, state,
                            keep_last=self.cfg.ckpt_keep_last, meta=meta,
                            io_hook=hook)
            except checkpoint.CheckpointError as e:
                event("checkpoint_failed", step=int(state.step),
                      error=str(e))
            return
        try:
            path = checkpoint.save(
                self.checkpoint_dir, state,
                retries=self.cfg.ckpt_retries,
                backoff_s=self.cfg.retry_backoff_s,
                keep_last=self.cfg.ckpt_keep_last,
                meta=meta, io_hook=hook, metrics=metrics,
                on_retry=lambda attempt, e: event(
                    "checkpoint_retry", step=int(state.step),
                    attempt=attempt, error=str(e)))
            event("checkpoint", step=int(state.step),
                  path=os.path.basename(path))
        except checkpoint.CheckpointError as e:
            event("checkpoint_failed", step=int(state.step), error=str(e))

    @staticmethod
    def _drain(writer, event) -> None:
        """Re-emit completed async-save outcomes as history events (on the
        training thread, keeping history single-writer)."""
        for ev in writer.drain_events():
            ev = dict(ev)
            event(ev.pop("event"), **ev)
