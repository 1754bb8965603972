"""Device idle under the trainer's phases (``bench/idle_phases.py`` and its
six readers) on a hand-made trace whose answers are known, and on the
recorded chip traces, which hold no ``trainer.*`` annotation."""

import glob
import gzip
import json
import os
import types

import pytest
from jax.profiler import ProfileData

from bench import harness, idle_phases, trace
from test_trace import DATA, space

READERS = {f"idle_{phase}_ms.{suffix}": (phase, sample)
           for phase in ("data", "dispatch", "sync_wait")
           for suffix, sample in (("img", "images"), ("tok", "tokens"))}

# two steps in a window of 100-2100 ns: step 1 at 100-1000, step 2 at
# 1000-2000, each with its data / dispatch / sync_wait / log phases
HOST = [("bench.window", 100, 2000),
        ("trainer.step", 100, 900), ("trainer.data", 100, 200),
        ("bench.data", 120, 160),
        ("trainer.dispatch", 300, 100), ("trainer.sync_wait", 400, 550),
        ("trainer.log", 950, 50),
        ("trainer.step", 1000, 1000), ("trainer.data", 1000, 200),
        ("bench.data", 1020, 160),
        ("trainer.dispatch", 1200, 50), ("trainer.sync_wait", 1250, 650),
        ("trainer.log", 1900, 100)]
DEVICES = {
    # busy 150-250 (input), 350-900 and 1300-1850 (steps); idle 100-150,
    # 250-350, 900-1300, 1850-2100
    "/device:TPU:0": {"XLA Ops": [("%fusion.1 = f32[8] fusion(%p)", 150, 100),
                                  ("%fusion.2 = f32[8] fusion(%p)", 350, 550),
                                  ("%fusion.3 = f32[8] fusion(%p)", 1300,
                                   550)]},
    # busy 100-1000; idle 1000-2100
    "/device:TPU:1": {"XLA Ops": [("%fusion.1 = f32[8] fusion(%p)", 100,
                                   900)]},
}
# idle ns under each phase on TPU:0 and TPU:1
EXPECTED_NS = {"data": (50 + 50 + 200, 200),
               "dispatch": (50 + 50, 50),
               "sync_wait": (50 + 50 + 50, 650)}


def ctx(host=HOST, sample="images", steps=2):
    red = trace.reduce(space({"/host:CPU": {"python": host}, **DEVICES}))
    return types.SimpleNamespace(sample=sample, trace=red, steps=steps)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_readers_on_a_known_trace(metric):
    phase, sample = READERS[metric]
    read = harness.reader(metric)
    want = 1e-6 * sum(EXPECTED_NS[phase]) / 2 / 2    # ms per step
    assert read(ctx(sample=sample)) == pytest.approx(want)
    other = "tokens" if sample == "images" else "images"
    assert read(ctx(sample=other)) is None
    assert read(types.SimpleNamespace(sample=sample, trace=None,
                                      steps=2)) is None


def test_phases_account_for_no_more_than_the_idle():
    c = ctx()
    per_step = 1e3 * sum(trace.length(d.gaps) for d in c.trace.devices) / \
        len(c.trace.devices) / c.steps
    parts = [idle_phases.idle_ms(c, "images", p) for p in EXPECTED_NS]
    assert sum(parts) <= per_step
    # what is left lies under log (150 ns on TPU:0, 100 on TPU:1) and
    # after the last step (100 on both)
    assert per_step - sum(parts) == pytest.approx(1e-6 * (250 + 200) / 2 / 2)


def test_first_step_data_phase_before_the_session():
    """The window opens inside the first step's data phase, whose
    ``trainer.data`` the profiler never records: its ``bench.data``
    counts instead."""
    host = [h for h in HOST if h[:2] not in (("trainer.step", 100),
                                             ("trainer.data", 100))]
    # TPU:0's gaps under bench.data 120-280: 120-150 and 250-280
    want = 1e-6 * ((30 + 30 + 200) + 200) / 2 / 2
    assert idle_phases.idle_ms(ctx(host), "images", "data") == \
        pytest.approx(want)
    # without any trainer.data the reading is None, bench.data or not
    host = [h for h in HOST if h[0] != "trainer.data"]
    assert idle_phases.idle_ms(ctx(host), "images", "data") is None


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.xplane.pb.gz"))))
def test_recorded_chip_traces_have_no_phases(path):
    with gzip.open(path) as f:
        red = trace.reduce(ProfileData.from_serialized_xspace(f.read()))
    sample = "tokens" if "mamba" in path else "images"
    c = types.SimpleNamespace(sample=sample, trace=red, steps=4)
    for metric in READERS:
        assert harness.reader(metric)(c) is None


def test_benchmark_lists_the_readers():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = {m["name"]: m for m in spec["per_layer"]}
    resnet = ["resnet50-b256-1chip", "resnet50-b32-4chip"]
    for metric, (_, sample) in READERS.items():
        m = entries[metric]
        assert (m["unit"], m["better"], m["source"]) == \
            ("ms", "lower", "program_span")
        assert m["workloads"] == (resnet if sample == "images"
                                  else ["mamba2-8l-s2048-1chip"])
