"""The trace reduction on a hand-made trace whose answers are known, and on
traces recorded on a TPU v5e by ``bench/run.py --trace 1 --keep-trace``
(``bench/tests/data``, gzipped)."""

import glob
import gzip
import os
import re

import pytest
from jax.profiler import ProfileData

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def space(planes):
    """XSpace text proto from {plane: {line: [(name, start_ns, dur_ns)]}}."""
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        body = []
        for lid, (lname, evs) in enumerate(lines.items(), 1):
            ev = " ".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                f"duration_ps: {d * 1000} }}" for n, s, d in evs)
            body.append(f'lines {{ id: {lid} name: "{lname}" '
                        f'timestamp_ns: 0 {ev} }}')
        meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in ids.items())
        out.append(f'planes {{ id: {pid} name: "{pname}" '
                   f'{" ".join(body)} {meta} }}')
    return ProfileData.from_text_proto("\n".join(out))


def test_reduction_of_a_known_trace():
    prof = space({
        "/host:CPU": {"python": [("bench.window", 100, 1000),
                                 ("$trainer.py:225 run", 100, 1000),
                                 ("bench.data", 150, 100),
                                 ("PjitFunction(step)", 700, 300)]},
        "/device:TPU:0": {
            "XLA Modules": [("jit_step(7)", 50, 650),
                            ("jit__augment_batch(3)", 900, 100)],
            "XLA Ops": [("%fusion.1 = f32[8] fusion(%p)", 50, 150),  # clipped
                        ("%convolution.2 = f32[8] convolution(%a, %b)", 180,
                         120),                            # overlaps fusion.1
                        # a collective by its opcode, not by its name
                        ("%psum.3 = f32[8] all-reduce(%c), to_apply=%add",
                         350, 100),
                        ("%all-reduce-start.4 = (f32[8], f32[8]) "
                         "all-reduce-start(%d)", 500, 10),
                        ("%fusion.5 = f32[8] fusion(%e)", 520, 60),  # hides some
                        ("%all-reduce-done.9 = f32[8] all-reduce-done("
                         "f32[8] %all-reduce-start.4)", 640, 10),
                        ("%gather.6 = f32[8] gather(%f, %g)", 900, 100),
                        ("%fusion.7 = f32[8] fusion(%h)", 1150, 100)]},
        "/device:TPU:1": {
            "XLA Modules": [("jit_step(7)", 100, 600)],
            "XLA Ops": [("%fusion.1 = f32[8] fusion(%p)", 100, 500)]},
    })
    red = trace.reduce(prof)
    assert red.window_s == pytest.approx(1000e-9)
    d0, d1 = red.devices
    # busy: 100-300, 350-450, 500-650 (the async pair), 900-1000
    assert d0.busy_s == pytest.approx(550e-9)
    assert d0.programs["jit_step"] == pytest.approx(450e-9)
    assert d0.programs["jit__augment_batch"] == pytest.approx(100e-9)
    # collectives: 350-450, and 500-650 for the async pair
    assert d0.collective_s == pytest.approx(250e-9)
    assert d0.collective_ops == {"psum.3", "all-reduce-start.4",
                                 "all-reduce-done.9"}
    assert d0.collective_exposed_s == pytest.approx(190e-9)
    assert d1.busy_s == pytest.approx(500e-9)
    assert red.busy_s == pytest.approx(525e-9)
    assert trace.top_ops(red, 1) == [("jit_step/fusion.1",
                                      pytest.approx(600e-9))]
    # the longest gaps: TPU:1 600-1100 (midpoint 850), TPU:0 650-900
    # (midpoint 775), both under the host's dispatch of the step
    assert trace.idle_gaps(red, 3) == [
        ("/device:TPU:1 PjitFunction(step)", pytest.approx(500e-9)),
        ("/device:TPU:0 PjitFunction(step)", pytest.approx(250e-9)),
        ("/device:TPU:0 $trainer.py:225 run", pytest.approx(100e-9))]


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(space({"/host:CPU": {"python": [("x", 0, 10)]}}))


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.xplane.pb.gz"))))
def test_recorded_chip_trace(path):
    with gzip.open(path) as f:
        prof = ProfileData.from_serialized_xspace(f.read())
    red = trace.reduce(prof)
    assert red.devices, "no device plane"
    # every op whose HLO text has a collective opcode is counted as one
    coll = re.compile(r" (all-reduce|all-gather|reduce-scatter|"
                      r"collective-permute|all-to-all)(-start|-done)?\(")
    w0, w1 = (t * 1e9 for t in red.window)
    planes = {p.name: p for p in prof.planes}
    for d in red.devices:
        ops = [ev.name for line in planes[d.name].lines
               if line.name == trace.OPS_LINE
               for ev in line.events if ev.end_ns > w0 and ev.start_ns < w1]
        assert d.collective_ops == {trace.op_name(t) for t in ops
                                    if coll.search(t.split(" = ", 1)[-1])}
    for d in red.devices:
        assert 0 < d.busy_s <= red.window_s
        assert sum(d.programs.values()) <= d.busy_s * (1 + 1e-9)
        assert d.collective_exposed_s <= d.collective_s <= d.busy_s
        idle = sum(e - s for s, e in d.gaps)
        assert idle + d.busy_s == pytest.approx(red.window_s, rel=1e-6)
    # what the traces showed on the chip (TPU v5 lite)
    progs = red.devices[0].programs
    if "4chip" in path:
        # the global batch is made and augmented on chip 0 only; every
        # chip runs the step and its all-reduces, none hidden
        assert len(red.devices) == 4
        assert progs["jit__augment_batch"] > 0.8 * red.devices[0].busy_s
        for d in red.devices:
            # 104 all-reduces, two of them named ``psum.<n>`` by JAX
            assert len(d.collective_ops) == 104
            assert {"psum.1723", "psum.1726"} <= d.collective_ops
            assert d.collective_s > 0
            assert d.collective_exposed_s == pytest.approx(d.collective_s)
        for d in red.devices[1:]:
            assert set(d.programs) == {"jit_step"}
    elif "resnet50" in path:
        assert progs["jit__augment_batch"] > 0.8 * red.busy_s
        assert 0.05 * red.busy_s < progs["jit_step"] < 0.2 * red.busy_s
    else:
        assert progs["jit_step"] > 0.99 * red.busy_s
