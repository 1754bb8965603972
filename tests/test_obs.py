"""Observability subsystem (repro.obs, docs/observability.md): metric
instrument semantics, crash-tolerant JSONL sinks (torn-tail + rotation),
nested span tracing with Chrome export, config fingerprints, and the
multidevice trainer smoke asserting the acceptance contract -- per-step
phase durations sum to the step wall time, per-bucket sync gauges match
the HLO bucket audit, the exported trace nests, and recording overhead
stays under 5% of a step."""

import json
import os
import re
import threading
import time

import numpy as np
import pytest

from repro.obs import ObsConfig, Telemetry, fingerprint
from repro.obs.metrics import (DEFAULT_TIME_EDGES_S, MetricsRegistry,
                               NULL_REGISTRY)
from repro.obs.sink import JsonlSink, read_jsonl, read_run, run_paths
from repro.obs.tracing import ANNOTATION_PREFIX, Tracer


# ------------------------------------------------------------- metrics --

def test_counter_monotonic_and_rejects_negative():
    reg = MetricsRegistry()
    c = reg.counter("train/steps")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # create-or-get: same instrument back
    assert reg.counter("train/steps") is c


def test_gauge_last_write_wins():
    reg = MetricsRegistry()
    g = reg.gauge("queue_depth")
    g.set(3)
    g.set(1)
    assert g.value == 1.0
    assert reg.snapshot()["queue_depth"] == {"type": "gauge", "value": 1.0}


def test_histogram_upper_bound_edge_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("lat", edges=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 3.0, 100.0):
        h.observe(v)
    snap = h.snapshot()
    # le-semantics: 0.5 and the exact tie 1.0 both land in le=1.0;
    # 3.0 in le=4.0; 100.0 overflows to +inf
    assert [b["count"] for b in snap["buckets"]] == [2, 0, 1, 1]
    assert snap["buckets"][-1]["le"] == "inf"
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(104.5)
    assert snap["min"] == 0.5 and snap["max"] == 100.0
    assert snap["mean"] == pytest.approx(104.5 / 4)


def test_histogram_edges_are_sorted_and_required():
    reg = MetricsRegistry()
    h = reg.histogram("x", edges=(4.0, 1.0, 2.0))
    assert h.edges == (1.0, 2.0, 4.0)
    with pytest.raises(ValueError):
        reg.histogram("empty", edges=())
    assert len(DEFAULT_TIME_EDGES_S) == 22


def test_registry_type_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("a")
    with pytest.raises(TypeError):
        reg.gauge("a")


def test_registry_names_prefix_filter():
    reg = MetricsRegistry()
    reg.counter("grad_sync/bucket00/nbytes")
    reg.counter("grad_sync/bucket01/nbytes")
    reg.counter("elastic/recoveries")
    assert reg.names("grad_sync/") == ["grad_sync/bucket00/nbytes",
                                       "grad_sync/bucket01/nbytes"]
    assert len(reg.names()) == 3


def test_metrics_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("h", edges=(0.5,))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000
    assert h.snapshot()["buckets"][-1]["count"] == 8000  # all overflow


def test_null_registry_accepts_everything_records_nothing():
    NULL_REGISTRY.counter("x").inc(5)
    NULL_REGISTRY.gauge("y").set(3)
    NULL_REGISTRY.histogram("z").observe(1.0)
    assert NULL_REGISTRY.snapshot() == {}
    assert NULL_REGISTRY.names() == []


# ---------------------------------------------------------------- sink --

def test_sink_stamping_and_header(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with JsonlSink(path, run_id="abc123", meta={"source": "test"}) as s:
        s.emit({"kind": "metric", "v": 1})
        s.emit({"kind": "event", "event": "x"})
    rows = read_jsonl(path)
    assert rows[0]["kind"] == "run_header"
    assert rows[0]["meta"] == {"source": "test"}
    assert [r["seq"] for r in rows] == [0, 1, 2]
    assert all(r["run_id"] == "abc123" for r in rows)
    ts = [r["t_s"] for r in rows]
    assert ts == sorted(ts) and ts[0] >= 0.0


def test_sink_payload_cannot_override_stamps(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with JsonlSink(path, run_id="realrun") as s:
        s.emit({"kind": "summary", "run_id": "realrun", "seq": 999})
    row = read_jsonl(path)[1]
    assert row["run_id"] == "realrun"
    assert row["seq"] == 1          # sink stamp, not the payload's 999


def test_sink_emit_after_close_raises(tmp_path):
    s = JsonlSink(str(tmp_path / "m.jsonl"))
    s.close()
    s.close()                       # idempotent
    with pytest.raises(ValueError):
        s.emit({"kind": "metric"})


def test_sink_rotation_chain_ordering(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with JsonlSink(path, rotate_bytes=300, meta={}) as s:
        for i in range(40):
            s.emit({"kind": "metric", "i": i})
    chain = run_paths(path)
    assert len(chain) > 2 and chain[-1] == path
    assert chain[0] == path + ".1"  # oldest first
    rows = read_run(path)
    assert [r["seq"] for r in rows] == list(range(41))  # header + 40
    assert [r["i"] for r in rows[1:]] == list(range(40))


def test_torn_tail_dropped_mid_file_corruption_handled(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with JsonlSink(path) as s:
        for i in range(5):
            s.emit({"kind": "metric", "i": i})
    # crash mid-write: a torn final line must be invisible to readers
    with open(path, "ab") as f:
        f.write(b'{"kind": "metr')
    rows = read_jsonl(path)
    assert len(rows) == 6 and rows[-1]["i"] == 4
    rows = read_jsonl(path, strict=True)    # a torn TAIL is fine even strict
    assert len(rows) == 6
    # mid-file garbage is real corruption: skipped lax, raised strict
    with open(path, "ab") as f:
        f.write(b'\n{"kind": "metric", "i": 99}\n')
    assert read_jsonl(path)[-1]["i"] == 99
    with pytest.raises(json.JSONDecodeError):
        read_jsonl(path, strict=True)


def test_sink_crash_consistency_any_truncation_point(tmp_path):
    """Chaos pattern: truncating the file at ANY byte offset must yield a
    clean prefix of the emitted records, never an exception -- the same
    either-old-or-new discipline as the checkpoint layer."""
    path = str(tmp_path / "m.jsonl")
    with JsonlSink(path) as s:
        for i in range(10):
            s.emit({"kind": "metric", "i": i, "pad": "x" * 7})
    blob = open(path, "rb").read()
    crash = str(tmp_path / "crash.jsonl")
    rng = np.random.RandomState(0)
    offsets = set(rng.randint(0, len(blob), size=50)) | {0, len(blob)}
    for cut in offsets:
        with open(crash, "wb") as f:
            f.write(blob[:cut])
        rows = read_jsonl(crash)
        assert [r["seq"] for r in rows] == list(range(len(rows)))


# ------------------------------------------------------------- tracing --

def test_span_nesting_depth_and_parent():
    tr = Tracer()
    with tr.span("step", step=3) as outer:
        with tr.span("sync/bucket3", step=3) as inner:
            time.sleep(0.002)
        assert inner.duration >= 0.002
    assert outer.depth == 0 and outer.parent is None
    assert inner.depth == 1 and inner.parent == "step"
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1 + 1e-6
    assert outer.duration >= inner.duration
    assert tr.spans("sync/bucket3", step=3) == [inner]


def test_span_exception_safety():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    sp = tr.spans("boom")[0]
    assert sp.error and sp.duration is not None
    with tr.span("after") as nxt:
        pass
    assert nxt.depth == 0              # stack unwound despite the raise


def test_disabled_tracer_yields_null_span():
    tr = Tracer(enabled=False)
    with tr.span("x") as sp:
        pass
    assert sp.duration == 0.0
    assert tr.spans() == []


def test_chrome_trace_export_loadable_and_nested(tmp_path):
    tr = Tracer()
    with tr.span("step", step=0):
        with tr.span("data", step=0):
            time.sleep(0.001)
        with tr.span("dispatch", step=0):
            time.sleep(0.001)
    path = str(tmp_path / "trace.json")
    n = tr.export_chrome_trace(path)
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert n == len(events) == 3
    assert all(e["ph"] == "X" for e in events)
    by_name = {e["name"]: e for e in events}
    step = by_name["step"]
    for child in ("data", "dispatch"):
        e = by_name[child]
        assert e["ts"] >= step["ts"]
        assert e["ts"] + e["dur"] <= step["ts"] + step["dur"] + 1.0  # µs
        assert e["args"]["step"] == 0


def _profiled(tmp_path, body):
    """Host events ``(line, name, start_ns, end_ns)`` of a CPU profiler
    session around ``body()``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    return [(line.name, ev.name, ev.start_ns, ev.end_ns)
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events]


def test_spans_are_profiler_annotations(tmp_path):
    """An enabled tracer's spans land in the profiler's trace as
    ``trainer.<name>``, nested as in the program; a disabled one's do
    not, and the tracer's own record keeps the plain names."""
    tr, off = Tracer(), Tracer(enabled=False)

    def body():
        with tr.span("step", step=0):
            with tr.span("data", step=0):
                time.sleep(0.002)
            with off.span("hidden"):
                pass

    events = _profiled(tmp_path, body)
    mine = {name: (line, s, e) for line, name, s, e in events
            if name.startswith(ANNOTATION_PREFIX)}
    assert set(mine) == {"trainer.step", "trainer.data"}
    (l_step, s0, e0), (l_data, s1, e1) = mine["trainer.step"], \
        mine["trainer.data"]
    assert l_step == l_data                 # one host line
    assert s0 <= s1 < e1 <= e0 and e1 - s1 >= 2e6
    assert [sp.name for sp in tr.spans()] == ["step", "data"]


def test_tracer_keeps_newest_spans_and_counts_drops(monkeypatch):
    from repro.obs import tracing

    monkeypatch.setattr(tracing, "MAX_SPANS", 4)
    reg = MetricsRegistry()
    tr = Tracer(metrics=reg)
    for k in range(6):
        with tr.span("step", step=k):
            pass
    assert [sp.step for sp in tr.spans()] == [2, 3, 4, 5]
    assert reg.counter("trace/dropped_spans").value == 2


# -------------------------------------------- compile and GC accounting --

def _fresh_compile(tag: float):
    """Compile (and run) a program no other test has compiled."""
    import jax
    import jax.numpy as jnp

    jax.jit(lambda x: x * tag + 1.0)(jnp.ones(3)).block_until_ready()


def _counts(tel):
    snap = tel.registry.snapshot()
    return tuple(snap.get(k, {}).get("value", 0)
                 for k in ("compile/count", "host/gc_collections"))


def test_gc_inside_a_span_is_counted():
    import gc

    tel = Telemetry()
    with tel.active(), tel.span("step", step=0):
        gc.collect()
    assert _counts(tel)[1] >= 1
    snap = tel.registry.snapshot()
    assert snap["host/gc_s"]["count"] == _counts(tel)[1]
    compiles, gc_s = tel.take_step_counts()
    assert gc_s > 0 and tel.take_step_counts() == (0, 0.0)


def test_telemetries_in_one_process_do_not_double_count():
    """Each compile and collection feeds the innermost active telemetry
    once, and no other; the process-wide hooks are registered once."""
    import gc

    import jax

    from repro import obs

    raw = []                # every backend compile the process reports

    def listener(event, secs, **_):
        if event == obs.COMPILE_EVENT:
            raw.append(secs)

    a, b = Telemetry(), Telemetry()
    jax.monitoring.register_event_duration_secs_listener(listener)
    gc.disable()            # no automatic collection inside the counts
    try:
        with a.active():
            _fresh_compile(2.5)
            gc.collect()
            assert _counts(a) == (len(raw), 1) and len(raw) >= 1
            assert _counts(b) == (0, 0)
            with b.active():             # the innermost is fed, alone
                n = len(raw)
                _fresh_compile(3.5)
                gc.collect()
                assert _counts(b) == (len(raw) - n, 1) and len(raw) > n
            assert _counts(a) == (n, 1)
            with a.active():             # re-entered: still counted once
                _fresh_compile(4.5)
            assert _counts(a)[0] + _counts(b)[0] == len(raw)
        seen = len(raw)
        _fresh_compile(5.5)              # nobody active: nobody counts
        gc.collect()
        assert len(raw) > seen
        assert _counts(a)[0] + _counts(b)[0] == seen
        assert _counts(a)[1] == 1 and _counts(b)[1] == 1
    finally:
        gc.enable()
        jax.monitoring.unregister_event_duration_listener(listener)
    for _ in range(3):
        with Telemetry().active():
            pass
    listeners = jax._src.monitoring.get_event_duration_listeners()
    assert listeners.count(obs._on_duration) == 1
    assert gc.callbacks.count(obs._on_gc) == 1
    # a disabled telemetry is never fed
    off = Telemetry(ObsConfig(enabled=False))
    with off.active():
        _fresh_compile(6.5)
    assert off.take_step_counts() == (0, 0.0)


# ------------------------------------------- stalled steps in the trainer --

def _tiny_trainer(n_steps, data_fn):
    """A 1-device MLP trainer of ``n_steps`` steps of 8 rows."""
    import jax
    import jax.numpy as jnp
    from repro.core.batch_control import build_plan
    from repro.core.grad_sync import GradSyncConfig
    from repro.core.schedules import BatchSchedule, BatchStage
    from repro.launch.train import device_mesh
    from repro.train.state import TrainState
    from repro.train.trainer import Trainer, TrainerConfig

    def loss_fn(params, batch, dp_axes):
        x, y = batch
        return (jnp.mean((jnp.tanh(x @ params["w"]) - y) ** 2),
                jnp.zeros((), jnp.float32))

    tcfg = TrainerConfig(grad_sync=GradSyncConfig(strategy="psum"),
                         log_every=1)
    plan = build_plan(BatchSchedule((BatchStage(0, 1.0, 8),)),
                      dataset_size=8 * n_steps, n_workers=1,
                      max_steps=n_steps)
    trainer = Trainer(mesh=device_mesh(jax.devices()[:1]),
                      dp_axes=("dy", "dx"), loss_fn=loss_fn, cfg=tcfg,
                      plan=plan, data_fn=data_fn, telemetry=Telemetry())
    state = TrainState.create({"w": jnp.eye(16, dtype=jnp.float32)})
    return trainer, state


def _rows(i, gb):
    rng = np.random.RandomState(i)
    return (rng.randn(gb, 16).astype(np.float32),
            rng.randn(gb, 16).astype(np.float32))


def test_trainer_reports_a_stalled_step_with_its_phase():
    """Every fetch sleeps 50 ms, the sixth 0.4 s: exactly that step is
    reported, with ``data`` its largest phase."""
    def slow_data(i, gb):
        time.sleep(0.4 if i == 5 else 0.05)
        return _rows(i, gb)

    lines = []
    trainer, state = _tiny_trainer(6, slow_data)
    _, history = trainer.run(state, log=lines.append)
    stalls = [h for h in history if h.get("event") == "step_stall"]
    assert len(stalls) == 1, stalls
    st = stalls[0]
    assert st["step"] == 6
    assert st["wall_s"] > 2 * st["median_s"]
    assert max(st["phases"], key=st["phases"].get) == "data"
    assert st["phases"]["data"] >= 0.4
    assert st["compiles"] == 0 and st["gc_s"] >= 0.0
    reg = trainer.telemetry.registry
    assert reg.counter("step/stalls").value == 1
    assert any(line.startswith("[step_stall] step=6") for line in lines)


def test_compile_count_flat_after_the_first_step():
    """The first step compiles; later steps of the same shape do not."""
    counts = []             # compile/count at each step's log line
    trainer, state = _tiny_trainer(4, _rows)
    reg = trainer.telemetry.registry
    trainer.run(state, log=lambda msg: counts.append(
        reg.counter("compile/count").value))
    assert len(counts) == 4
    assert counts[0] >= 1
    assert counts[1:] == [counts[0]] * 3


@pytest.mark.multidevice
def test_trainer_reads_where_the_batch_lives():
    """``data/batch_devices`` and ``data/resharded_batches``: the ResNet
    job's batch program on four devices makes each batch where the step
    takes it (4 devices, no reshard); the same batches placed on one device
    read 1 and one reshard per step."""
    import jax
    from repro.launch.train import device_mesh, resnet_job
    from repro.models import resnet

    trainer, state, data_fn = resnet_job(
        resnet.ResNetConfig.tiny(image_size=16), per_chip_batches=(2,),
        steps_per_stage=2, mesh=device_mesh(jax.devices()[:4]))

    def on_one_device(i, gb):       # uncommitted, as eager ops leave it
        return jax.device_put(jax.device_get(data_fn(i, gb)))

    for fn, devices, resharded in [(data_fn, 4, 0), (on_one_device, 1, 2)]:
        trainer.data_fn, trainer.telemetry = fn, Telemetry()
        state, _ = trainer.run(state, log=lambda *a: None)
        reg = trainer.telemetry.registry
        assert reg.counter("train/steps").value == 2
        assert reg.gauge("data/batch_devices").value == devices
        assert reg.counter("data/resharded_batches").value == resharded


def test_step_hlo_carries_named_scopes():
    """The step's lowered HLO names its four parts in ``op_name``
    metadata; the backward ops carry ``transpose(jvp(forward))``."""
    import jax
    import jax.numpy as jnp
    from repro.train.trainer import make_train_step

    trainer, state = _tiny_trainer(1, _rows)
    fn = make_train_step(trainer.loss_fn, trainer.mesh, trainer.dp_axes,
                         trainer.cfg, donate=False)
    hlo = fn.lower(jax.device_put(state), _rows(0, 8),
                   jnp.asarray(0.0, jnp.float32),
                   jnp.asarray(8.0, jnp.float32)).as_text(
                       dialect="hlo", debug_info=True)
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("forward", "exchange", "guard", "lars"):
        # the forward pass is differentiated: ``jvp(forward)``
        assert any(re.search(rf"[/(]{scope}[)/]", n) for n in names), scope
    assert any("transpose(jvp(forward))" in n for n in names)


# -------------------------------------------------- fingerprint/telemetry --

def test_fingerprint_deterministic_and_key_order_free():
    a = fingerprint({"x": 1, "y": [1, 2], "z": "s"})
    b = fingerprint({"z": "s", "y": [1, 2], "x": 1})
    assert a == b and len(a) == 12
    assert fingerprint({"x": 2, "y": [1, 2], "z": "s"}) != a


def test_telemetry_events_summary_and_idempotent_close(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = Telemetry(ObsConfig(metrics_path=path,
                              trace_path=str(tmp_path / "t.json")),
                    meta={"source": "test"})
    with tel.span("step", step=0):
        pass
    rec = tel.event("elastic_recovery", step=4)
    assert rec == {"kind": "event", "event": "elastic_recovery", "step": 4}
    tel.close()
    tel.close()
    rows = read_run(path)
    assert rows[-1]["kind"] == "summary"
    m = rows[-1]["metrics"]
    assert m["events/elastic_recovery"]["value"] == 1
    assert os.path.exists(str(tmp_path / "t.json"))


def test_telemetry_disabled_is_inert(tmp_path):
    tel = Telemetry(ObsConfig(enabled=False,
                              metrics_path=str(tmp_path / "no.jsonl")))
    assert tel.registry is NULL_REGISTRY
    assert tel.sink is None
    with tel.span("x") as sp:
        pass
    assert sp.duration == 0.0
    tel.event("whatever")
    tel.close()
    assert not os.path.exists(str(tmp_path / "no.jsonl"))


def test_record_bucket_metrics_gauges():
    import jax.numpy as jnp
    from repro.core.grad_sync import GradSyncConfig, record_bucket_metrics

    tree = {f"layer{i:02d}": {"kernel": np.zeros((64, 64), np.float32)}
            for i in range(4)}
    cfg = GradSyncConfig(fuse=True, comm_dtype=jnp.float32,
                         bucket_bytes=16 * 1024)
    reg = MetricsRegistry()
    layout = record_bucket_metrics(tree, cfg, reg)
    assert len(layout) == 4
    snap = reg.snapshot()
    assert snap["grad_sync/num_buckets"]["value"] == 4
    assert snap["grad_sync/num_exchanges"]["value"] == 4
    assert snap["grad_sync/total_nbytes"]["value"] == 4 * 64 * 64 * 4
    assert snap["grad_sync/bucket00/nbytes"]["value"] == 64 * 64 * 4
    # per-leaf sync (fuse=False): every large kernel is its own strategy
    # exchange; no small leaves here, so zero grouped buckets
    reg2 = MetricsRegistry()
    layout2 = record_bucket_metrics(
        tree, GradSyncConfig(fuse=False, comm_dtype=jnp.float32), reg2)
    assert [b["mode"] for b in layout2] == ["per_leaf"] * 4
    snap2 = reg2.snapshot()
    assert snap2["grad_sync/num_exchanges"]["value"] == 4
    assert snap2["grad_sync/per_leaf_exchanges"]["value"] == 4
    assert snap2["grad_sync/grouped_buckets"]["value"] == 0
    assert record_bucket_metrics(tree, cfg, None) == []


def test_record_bucket_metrics_clears_stale_gauges():
    """An elastic re-resolve that shrinks the schedule (or switches the
    sync path) must not leave the previous run's per-bucket gauges in the
    registry -- they would be exported as current (ISSUE 10 bugfix)."""
    import jax.numpy as jnp
    from repro.core.grad_sync import GradSyncConfig, record_bucket_metrics

    tree = {f"layer{i:02d}": {"kernel": np.zeros((64, 64), np.float32)}
            for i in range(4)}
    reg = MetricsRegistry()
    record_bucket_metrics(
        tree, GradSyncConfig(fuse=True, comm_dtype=jnp.float32,
                             bucket_bytes=16 * 1024), reg)
    assert "grad_sync/bucket03/nbytes" in reg.names("grad_sync/")
    # re-resolve to the fully-fused schedule: one bucket
    record_bucket_metrics(
        tree, GradSyncConfig(fuse=True, comm_dtype=jnp.float32,
                             bucket_bytes=0), reg)
    names = reg.names("grad_sync/")
    assert "grad_sync/bucket00/nbytes" in names
    assert "grad_sync/bucket03/nbytes" not in names
    assert reg.snapshot()["grad_sync/num_buckets"]["value"] == 1
    # switch to the per-leaf path: fused-only gauges must not linger
    record_bucket_metrics(
        tree, GradSyncConfig(fuse=False, comm_dtype=jnp.float32), reg)
    names = reg.names("grad_sync/")
    assert "grad_sync/num_buckets" not in names
    assert "grad_sync/bucket00/nbytes" not in names
    assert reg.snapshot()["grad_sync/per_leaf_exchanges"]["value"] == 4


def test_registry_remove_prefix():
    reg = MetricsRegistry()
    reg.counter("a/x").inc()
    reg.gauge("a/y").set(2)
    reg.gauge("ab").set(3)
    reg.gauge("b/z").set(4)
    assert reg.remove_prefix("a/") == 2
    assert reg.names() == ["ab", "b/z"]
    assert reg.remove_prefix("nope/") == 0
    with pytest.raises(ValueError):
        reg.remove_prefix("")


# ------------------------------------------- trainer smoke (acceptance) --

@pytest.mark.multidevice
def test_trainer_telemetry_end_to_end(tmp_path):
    """The acceptance contract on a real 8-device run: (a) per-step phase
    durations sum to within 10% of step wall time, (b) per-bucket sync
    gauges == the HLO bucket audit's exchange count, (c) the Chrome trace
    loads and nests data/dispatch/checkpoint under step, (d) recording
    overhead < 5% of a step, (e) history rows round-trip through JSONL on
    their ``kind`` marker."""
    import jax
    import jax.numpy as jnp
    from repro.core.grad_sync import GradSyncConfig
    from repro.core.schedules import BatchSchedule, BatchStage
    from repro.core.batch_control import build_plan
    from repro.launch import hlo_stats
    from repro.train.state import TrainState
    from repro.train.trainer import Trainer, TrainerConfig, make_train_step

    mesh = jax.make_mesh((2, 4), ("dy", "dx"))
    n_layers, width = 8, 64

    # comm-group-only params (no bn/bias/scale): with 16 KiB buckets every
    # 64x64 fp32 kernel is its own bucket -> exactly n_layers exchanges
    def init_params(key):
        keys = jax.random.split(key, n_layers)
        return {f"layer{i:02d}":
                {"kernel": jax.random.normal(keys[i], (width, width),
                                             jnp.float32) / width}
                for i in range(n_layers)}

    def loss_fn(params, batch, dp_axes):
        x, y = batch
        h = x
        for i in range(n_layers):
            h = jnp.tanh(h @ params[f"layer{i:02d}"]["kernel"])
        return (jnp.mean((h - y) ** 2), jnp.zeros((), jnp.float32))

    rng = np.random.RandomState(0)
    xs = rng.randn(512, width).astype(np.float32)
    ys = np.tanh(xs @ rng.randn(width, width).astype(np.float32) / width)

    def data_fn(i, gb):
        idx = (np.arange(gb) + i * gb) % len(xs)
        return xs[idx], ys[idx]

    metrics_path = str(tmp_path / "metrics.jsonl")
    trace_path = str(tmp_path / "trace.json")
    gcfg = GradSyncConfig(strategy="torus2d", fuse=True,
                          comm_dtype=jnp.float32, bucket_bytes=16 * 1024)
    tcfg = TrainerConfig(
        grad_sync=gcfg, log_every=2, ckpt_every_steps=2,
        obs=ObsConfig(metrics_path=metrics_path, trace_path=trace_path))
    plan = build_plan(BatchSchedule((BatchStage(0, 1.0, 2),)),
                      dataset_size=512, n_workers=8, max_steps=6)
    trainer = Trainer(mesh=mesh, dp_axes=("dy", "dx"), loss_fn=loss_fn,
                      cfg=tcfg, plan=plan, data_fn=data_fn,
                      checkpoint_dir=str(tmp_path / "ckpt"))
    state = TrainState.create(init_params(jax.random.key(0)))
    state, history = trainer.run(state, log=lambda *a: None)
    assert int(state.step) == 6

    rows = read_run(metrics_path)
    summary = [r for r in rows if r["kind"] == "summary"][-1]
    snap = summary["metrics"]

    # (a) phase coverage: the spans account for the step's wall time
    phase_rows = [r for r in rows if r.get("metric") == "step_phases"]
    assert len(phase_rows) == 6
    for r in phase_rows:
        covered = sum(r["phases"].values())
        assert covered >= 0.90 * r["wall_s"], (r["step"], r)
        assert covered <= 1.02 * r["wall_s"], (r["step"], r)

    # (b) per-bucket gauges == the compiled HLO's independent exchanges
    bucket_gauges = [n for n in snap
                    if n.startswith("grad_sync/bucket")
                    and n.endswith("/nbytes")]
    assert len(bucket_gauges) == n_layers
    assert snap["grad_sync/num_buckets"]["value"] == n_layers
    fn = make_train_step(loss_fn, mesh, ("dy", "dx"), tcfg, donate=False)
    batch = data_fn(0, 16)
    hlo = fn.lower(state, batch, jnp.asarray(0.0, jnp.float32),
                   jnp.asarray(16.0, jnp.float32)).compile().as_text()
    audit = hlo_stats.bucket_audit(hlo, min_bytes=1024)
    assert audit["num_exchanges"] == len(bucket_gauges)

    # (c) the Chrome trace loads and nests
    doc = json.load(open(trace_path))
    events = doc["traceEvents"]
    names = {e["name"] for e in events}
    assert {"step", "data", "dispatch", "sync_wait",
            "checkpoint"} <= names
    steps = sorted((e for e in events if e["name"] == "step"),
                   key=lambda e: e["ts"])
    assert len(steps) == 6
    s0 = steps[0]
    inner = [e for e in events if e["name"] in ("data", "dispatch")
             and s0["ts"] <= e["ts"] <= s0["ts"] + s0["dur"]]
    assert len(inner) >= 2
    for e in inner:
        assert e["ts"] + e["dur"] <= s0["ts"] + s0["dur"] + 1.0

    # (d) recording overhead: microbench the per-step telemetry bundle
    # (the spans + observes + emits _run_steps adds) against the mean
    # post-compile step wall time
    tel = Telemetry(ObsConfig(metrics_path=str(tmp_path / "bench.jsonl")))
    reg = tel.registry
    n_iters = 200
    t0 = time.perf_counter()
    for k in range(n_iters):
        with tel.span("step", step=k) as sp:
            with tel.span("data", step=k):
                pass
            with tel.span("dispatch", step=k):
                pass
            with tel.span("sync_wait", step=k):
                pass
            with tel.span("log", step=k):
                pass
            with tel.span("checkpoint", step=k):
                pass
        reg.histogram("step/wall_s").observe(sp.duration)
        reg.histogram("step/data_s").observe(0.0)
        reg.histogram("step/sync_wait_s").observe(0.0)
        reg.counter("train/steps").inc()
        reg.gauge("train/loss_scale").set(1.0)
        tel.emit({"kind": "metric", "metric": "step_phases", "step": k,
                  "wall_s": sp.duration, "phases": {"data": 0.0}})
    per_bundle = (time.perf_counter() - t0) / n_iters
    tel.close()
    steady = [r["wall_s"] for r in phase_rows[1:]]   # drop the compile step
    mean_step = sum(steady) / len(steady)
    assert per_bundle < 0.05 * mean_step, (per_bundle, mean_step)

    # (e) history kinds round-trip through JSONL
    assert all(h.get("kind") in ("metric", "event") for h in history)
    blob = "\n".join(json.dumps(h) for h in history)
    back = [json.loads(line) for line in blob.splitlines()]
    assert back == history
    assert {h["kind"] for h in back} == {"metric", "event"}
    events_h = [h for h in back if h["kind"] == "event"]
    assert any(e["event"] == "checkpoint" for e in events_h)
    # sink mirrored every history row (by kind count)
    mirrored = [r for r in rows
                if r["kind"] in ("metric", "event")
                and r.get("metric") != "step_phases"]
    assert len(mirrored) == len(history)
