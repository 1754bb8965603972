"""Whole benchmark runs on the CPU at small sizes, past the harness's look
for a chip: a sound run comes out correct, a run with the timed path
broken underneath comes out not correct, and so does the control (the
reference in float8 in the program's place)."""

import time

import jax
import pytest

from bench import calibrate, compare, harness

CELLS = {"resnet-1": ("resnet50-b256-1chip", 1),
         "resnet-4": ("resnet50-b32-4chip", 4),
         "lm-1": ("mamba2-8l-s2048-1chip", 1)}
# Limits at these small sizes, between what sound runs and the control
# read on the CPU: the cells' own limits are set from chip readings at the
# cells' sizes (PERF.md), where bf16 rounding averages out further.
TINY_LIMITS = {"resnet": {"loss": 2e-3, "update1": 0.3, "change3": 0.3},
               "lm": {"loss": 1e-4, "update1": 3e-3, "change3": 0.03}}


def tiny(which):
    name, chips = CELLS[which]
    cell = harness.Cell.load(name)
    if cell.config["family"] == "resnet":
        cell.config.update(stage_sizes=[1, 1], width=8, num_classes=10,
                           image_size=32)
        cell.traffic.update(per_chip_batch=8)
    else:
        cell.config.update(d_model=64, n_layers=2, ssm_state=16,
                           ssm_head_dim=16, ssm_chunk=16, vocab=128)
        cell.traffic.update(per_chip_batch=4, seq_len=64)
    cell.limits = TINY_LIMITS[cell.config["family"]]
    return cell, jax.devices()[:chips]


def run(which, seed, fault=""):
    cell, devices = tiny(which)
    undo = calibrate.plant(fault)
    try:
        return harness.run(cell, seed, 0.5, False, devices,
                           time.perf_counter())
    finally:
        undo()


@pytest.mark.parametrize("which", sorted(CELLS))
def test_sound_run_is_correct(which, capsys):
    out = run(which, 2**33 + 7)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] == CELLS[which][1]
    e2e = {"lm-1": "tokens_per_s"}.get(which, "images_per_s")
    assert set(out["metrics"]) == {e2e, "setup_s"}
    assert "compile or trace events inside: 0" in capsys.readouterr().err


@pytest.mark.parametrize("which,fault", [
    ("resnet-1", "frozen"), ("resnet-1", "half_batch"),
    ("resnet-4", "no_exchange"), ("resnet-4", "half_batch"),
    ("lm-1", "frozen"), ("lm-1", "half_batch")])
def test_broken_step_is_not_correct(which, fault):
    out = run(which, 11, fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("which", ["resnet-1", "lm-1"])
def test_control_is_not_correct(which):
    cell, devices = tiny(which)
    job = harness.Job(cell, devices)
    job.first_steps(5, lambda m: None)
    ref = job.reference(5)
    control = job.reference(5, quant="fp8")
    assert not compare.passed(compare.checks(control, ref, cell.limits))
