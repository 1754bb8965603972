"""Run one benchmark cell once: set-up, a measured window through the
program's ``Trainer.run``, the comparison that decides ``correct``, and the
result line.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell, its configuration file (whose ``family`` picks ``bench/jobs/<family>.py``),
its traffic file ``bench/traffic/<traffic>.json``, its limits file
``bench/limits/<cell>.json``, and each metric's reader
``bench/metrics/<metric>.py``.

The run, on one ``Trainer`` built by the program's job:

1. The weights are made on the device from the seed, in one jitted call,
   by the plain reference's ``init``; the data stream is the job's own
   ``data_fn`` at step indices offset by the seed.
2. ``Trainer.run`` for one step, then for two more (``log_every`` 1 in
   both, so each step's loss is logged). The state after step 1 gives each
   leaf's first update, the state after step 3 each leaf's change. Both
   calls build the step anew (``Trainer.run`` makes its jitted step per
   call); the executable comes from the compile cache.
3. The window: one more ``Trainer.run``. Its first ``warm_steps`` steps
   absorb that call's trace; the window opens at the next step's
   ``data_fn`` call and closes at the first ``data_fn`` call after
   ``--seconds`` (the harness raises ``StopWindow`` from its wrapper of
   ``data_fn``), so it holds whole steps only. A traced run traces at most
   ``trace_steps`` steps of it.
4. The program's state is freed, and the reference trains the same three
   steps from the same weights on the same batches.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CHECKED_STEPS = 3
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits",
                  "/jax/compilation_cache/cache_misses")


def err(*a):
    print(*a, file=sys.stderr, flush=True)


class StopWindow(Exception):
    """Raised from the data wrapper to end the measured window."""


# -- what BENCHMARK.json and the data files say about a cell ---------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    family: object

    @staticmethod
    def load(name: str, root: str = ROOT) -> "Cell":
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            spec = json.load(f)
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; "
                             f"options: {sorted(cells)}")
        w = cells[name]
        conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
        config = _json(os.path.join(root, conf["file"]))
        traffic = _json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        limits = _json(os.path.join(BENCH, "limits", name + ".json"))

        def mine(m):
            return name in m.get("workloads", [name])

        e2e = [m for m in spec["end_to_end"] if mine(m)]
        reported = {m["name"] for m in e2e}
        layer = [m for m in spec["per_layer"]
                 if mine(m) and m["moves"] in reported]
        family = importlib.import_module(f"bench.jobs.{config['family']}")
        return Cell(name, w["chips"], config, traffic, limits, e2e, layer,
                    family)


def _json(path):
    with open(path) as f:
        return json.load(f)


def reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the feed and the window ----------------------------------------------

class Feed:
    """Wraps the job's ``data_fn``: step indices offset by the seed, every
    call counted, the first ``keep`` batches copied to the host for the
    reference, and ``on_step(gstep)`` called before each fetch."""

    def __init__(self, data_fn, offset: int, keep: int):
        self.data_fn, self.offset, self.keep = data_fn, offset, keep
        self.calls = 0
        self.kept = []
        self.on_step = None

    def __call__(self, gstep, gb):
        import jax

        if self.on_step is not None:
            self.on_step(gstep)
        with jax.profiler.TraceAnnotation("bench.data"):
            batch = self.data_fn(self.offset + self.calls, gb)
        self.calls += 1
        if len(self.kept) < self.keep:
            self.kept.append(jax.device_get(batch))
        return batch


class Window:
    """Opens at step ``warm`` of the window's ``Trainer.run`` and closes at
    the first step that starts ``seconds`` after it opened (or after
    ``trace_steps`` steps when tracing)."""

    def __init__(self, warm, seconds, trace_dir=None, trace_steps=None):
        self.warm, self.seconds = warm, seconds
        self.trace_dir, self.trace_steps = trace_dir, trace_steps
        self.t0 = self.t1 = None
        self.steps = 0
        self._ann = None

    def __call__(self, gstep):
        import jax

        now = time.perf_counter()
        if gstep == self.warm:
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
                self._ann = jax.profiler.TraceAnnotation("bench.window")
                self._ann.__enter__()
            self.t0 = time.perf_counter()
            return
        if gstep < self.warm:
            return
        done = gstep - self.warm
        if (now - self.t0 >= self.seconds
                or (self.trace_dir and done >= self.trace_steps)):
            self.t1 = now
            self.steps = done
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
            raise StopWindow()


class CompileLog:
    """Every compile, trace and compile-cache event, with its time."""

    def __init__(self):
        import jax

        self.events = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if name in COMPILE_EVENTS:
            self.events.append((time.perf_counter(), name, 0.0))

    def _duration(self, name, secs, **kw):
        if name in COMPILE_EVENTS:
            self.events.append((time.perf_counter(), name, secs))

    def count(self, t0, t1):
        return sum(1 for t, _, _ in self.events if t0 <= t < t1)

    def names(self, t0, t1) -> dict:
        out = {}
        for t, n, _ in self.events:
            if t0 <= t < t1:
                out[n.rsplit("/", 1)[-1]] = out.get(n.rsplit("/", 1)[-1], 0) + 1
        return out

    def compile_s(self, t0, t1):
        return sum(s for t, n, s in self.events if t0 <= t < t1
                   and n == "/jax/core/compile/backend_compile_duration")


# -- readings of the program's state ---------------------------------------

def memory_peak(devices) -> int:
    """Peak device memory on the fullest chip: the allocator's peak of
    buffers in use plus its peak of memory reserved for the programs'
    temporaries (the TPU runtime keeps those apart; ``peak_bytes_in_use``
    alone leaves out the step's activations)."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        err(f"[memory] {d}: " + json.dumps(st, sort_keys=True))
        reserved = st.get("peak_bytes_reserved", st.get("bytes_reserved", 0))
        peaks.append(int(st.get("peak_bytes_in_use", 0)) + int(reserved))
    return max(peaks)


def data_offset(seed: int) -> int:
    """Step index at which this seed's data stream starts: well inside the
    32-bit range that ``fold_in`` takes, 1024 steps apart."""
    return (int(seed) % 2_000_000) * 1024


class Job:
    """The program's job for a cell, built once: its ``Trainer`` with a
    harness-owned ``Telemetry`` and the seeded ``Feed`` around its
    ``data_fn``, and the jitted weight maker."""

    def __init__(self, cell: Cell, devices):
        import jax

        from bench.reference import common
        from repro.launch.train import device_mesh
        from repro.obs import ObsConfig, Telemetry

        self.cell = cell
        self.mesh = device_mesh(devices)
        self.trainer, data_fn = cell.family.build(cell.config, cell.traffic,
                                                  self.mesh)
        self.tel = Telemetry(ObsConfig())
        self.trainer.telemetry = self.tel
        self.feed = Feed(data_fn, 0, keep=CHECKED_STEPS)
        self.trainer.data_fn = self.feed
        self.make = weight_maker(cell, self.mesh)
        self.norms = jax.jit(common.leaf_norms)
        self.change = jax.jit(lambda a, b: common.leaf_norms(
            jax.tree.map(jax.numpy.subtract, a, b)))
        t = cell.traffic
        self.per_step = t["per_chip_batch"] * cell.chips
        self.epoch_size = cell.family.epoch_samples(cell.config, t,
                                                    cell.chips)

    def first_steps(self, seed: int, log, marks=None):
        """Weights from ``seed``, then ``Trainer.run`` for step 1 and for
        steps 2-3 (logging every step). Returns the state after step 3 and
        the program's readings: each step's loss, each leaf's first update
        and each leaf's change after three steps."""
        import jax

        from bench.reference import common
        from repro.train.state import TrainState

        marks = {} if marks is None else marks
        tr, feed = self.trainer, self.feed
        feed.offset, feed.calls, feed.kept = data_offset(seed), 0, []
        key = common.key_from_seed(seed)
        state = jax.block_until_ready(TrainState.create(self.make(key)))
        marks["weights"] = time.perf_counter()
        log_every = tr.cfg.log_every
        tr.cfg = dataclasses.replace(tr.cfg, log_every=1)
        try:
            # each call restarts the plan at its step 0
            state, h1 = tr.run(state, max_steps=1, log=log)
            update1 = np.asarray(self.norms(state.opt_state["momentum"]))
            marks["step1"] = time.perf_counter()
            state, h2 = tr.run(state, max_steps=CHECKED_STEPS - 1, log=log)
        finally:
            tr.cfg = dataclasses.replace(tr.cfg, log_every=log_every)
        w0 = self.make(key)
        change3 = np.asarray(self.change(state.params, w0))
        del w0
        marks["step3"] = time.perf_counter()
        rows = [r for r in h1 + h2 if r.get("kind") == "metric"]
        prog = {"loss": np.asarray([r["loss"] for r in rows]),
                "update1": update1, "change3": change3,
                "skipped": sum(int(r["skipped"]) for r in rows)}
        return state, prog

    @property
    def leaf_names(self):
        import jax

        from bench.reference import common

        tree = jax.eval_shape(lambda: self.cell.family.init(
            jax.random.key(0), self.cell.config))
        return [common.path_name(p) for p, _ in
                jax.tree_util.tree_flatten_with_path(tree)[0]]

    @property
    def epochs(self):
        """The epoch each checked step trains at: step 1 at the plan's step
        0, steps 2-3 at its steps 0 and 1."""
        return [0.0] + [i * self.per_step / self.epoch_size
                        for i in range(CHECKED_STEPS - 1)]

    def reference(self, seed, quant=None):
        from bench.reference import common

        return reference_readings(self.cell, common.key_from_seed(seed),
                                  self.feed.kept, self.epochs, self.per_step,
                                  self.mesh, quant)


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, keep_trace: str | None = None) -> dict:
    import jax

    from bench import compare

    clog = CompileLog()
    marks = {"jax": time.perf_counter()}
    job = Job(cell, devices)
    marks["job"] = time.perf_counter()

    def log(msg):
        err(f"  {msg}")

    state, prog = job.first_steps(seed, log, marks)

    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
    traffic = cell.traffic
    window = Window(traffic["warm_steps"], seconds, trace_dir,
                    traffic["trace_steps"])
    tel, reg = job.tel, job.tel.registry
    job.feed.on_step = window
    n_spans = len(tel.tracer.spans("step"))
    skipped_before = reg.counter("train/skipped_steps").value
    try:
        job.trainer.run(state, log=log)
        raise RuntimeError("the plan ended before the window closed")
    except StopWindow:
        pass
    finally:
        job.feed.on_step = None
    del state
    if trace_dir:
        jax.profiler.stop_trace()
    window_s = window.t1 - window.t0
    in_window = clog.count(window.t0, window.t1)
    failed = int(reg.counter("train/skipped_steps").value - skipped_before)
    mem = memory_peak(devices)
    gc.collect()

    spans = {}
    win_steps = [s for s in tel.tracer.spans("step")[n_spans:]
                 if window.warm <= s.step < window.warm + window.steps]
    if win_steps:
        lo, hi = win_steps[0].t0, win_steps[-1].t1
        for sp in tel.tracer.spans():
            if sp.step is not None and lo <= sp.t0 and sp.t1 <= hi:
                spans.setdefault(sp.name, []).append(sp.duration)
    setup = window.t0 - t_start
    err(f"[setup] jax import+init {marks['jax'] - t_start:.3f} s, job "
        f"{marks['job'] - marks['jax']:.3f} s, weights "
        f"{marks['weights'] - marks['job']:.3f} s, step 1 "
        f"{marks['step1'] - marks['weights']:.3f} s, steps 2-3 "
        f"{marks['step3'] - marks['step1']:.3f} s, warm "
        f"{window.t0 - marks['step3']:.3f} s; total {setup:.3f} s; "
        f"compile or trace events before the window "
        f"{json.dumps(clog.names(0, window.t0))}, "
        f"{clog.compile_s(0, window.t0):.3f} s in backend compiles or cache loads")
    err(f"[window] {window.steps} steps in {window_s:.4f} s; compile or "
        f"trace events inside: {in_window}")
    walls = np.asarray([s.duration for s in win_steps])
    if len(walls):
        err(f"[window] step wall (s) median {np.median(walls):.6f} min "
            f"{walls.min():.6f} max {walls.max():.6f}; first 100: "
            + " ".join(f"{w:.4f}" for w in walls[:100]))

    fam, chips = cell.family, cell.chips
    ctx = Context(cell=cell, window_s=window_s, steps=window.steps,
                  samples_per_step=fam.samples_per_step(cell.config, traffic,
                                                        chips),
                  setup_s=setup, spans=spans,
                  flops_per_step=fam.flops_per_step(cell.config, traffic,
                                                    chips),
                  chips=chips, sample=fam.SAMPLE,
                  step_program=fam.STEP_PROGRAM,
                  device_kind=devices[0].device_kind, trace=None)
    breakdown = None
    if trace_dir:
        from bench import trace as tr
        red = tr.reduce(_profile(trace_dir))
        ctx.trace = red
        breakdown = {"device_ops": [[k, v] for k, v in tr.top_ops(red)],
                     "idle_gaps": [[k, v] for k, v in tr.idle_gaps(red)]}
        err(f"[trace] {trace_dir}: window {red.window_s:.6f} s, busy "
            f"{red.busy_s:.6f} s per device over {len(red.devices)} devices")
        for d in red.devices:
            err(f"[trace] {d.name}: programs (s) "
                + json.dumps({k: round(v, 6) for k, v in
                              sorted(d.programs.items(),
                                     key=lambda kv: -kv[1])[:12]}))
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    t = time.perf_counter()
    refr = job.reference(seed)
    err(f"[reference] three steps in {time.perf_counter() - t:.3f} s")
    checks = compare.checks(prog, refr, cell.limits)
    correct = (compare.passed(checks) and prog["skipped"] == 0
               and failed == 0 and window.steps > 0)
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": mem}
    if trace_dir:
        dev["busy_s"] = ctx.trace.busy_s
        dev["window_s"] = ctx.trace.window_s
    out = {"correct": bool(correct), "attempted": int(window.steps),
           "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in checks.items()}
    for k, v in checks.items():
        err(f"check {k} {v['value']!r} limit {v['limit']!r}")
    return out


def weight_maker(cell, mesh):
    """The seed key's weights, made on the device in one jitted call by the
    plain reference's ``init`` and replicated over ``mesh``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.jit(lambda k: cell.family.init(k, cell.config),
                   out_shardings=NamedSharding(mesh, P()))


def reference_readings(cell, key, batches, epochs, global_batch, mesh,
                       quant=None):
    """The plain reference's three steps from the same weights and
    batches, with the batch split over the cell's chips by rows. The
    reference runs on the mesh's devices with automatic axes, so its
    weights are made there: arrays of the program's (Explicit) mesh do not
    mix with them."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench.reference import common

    mesh = Mesh(mesh.devices, mesh.axis_names)      # automatic axes
    rows = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    placed = [jax.device_put(b, rows) for b in batches]
    make = weight_maker(cell, mesh)
    with jax.default_matmul_precision("highest"):
        return common.train_readings(
            cell.family.reference_loss(cell.config, quant),
            lambda: make(key), placed, epochs, global_batch,
            cell.config["recipe"], cell.family.REFERENCE_ROWS)


@dataclasses.dataclass
class Context:
    """What a metric's reader may read."""
    cell: Cell
    window_s: float
    steps: int
    samples_per_step: int
    setup_s: float
    spans: dict
    flops_per_step: float
    chips: int
    sample: str
    step_program: str
    device_kind: str
    trace: object

    @property
    def peak(self) -> dict:
        return peak(self.device_kind)


def peak(kind: str) -> dict:
    table = _json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise SystemExit(f"no peak numbers for device kind {kind!r}; "
                         f"add it to bench/peaks.json with its source")
    return table[kind]


def _profile(trace_dir):
    import glob

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])
