"""Readings that the limits in ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \\
        [--control-seeds 1,2,3] [--fault half_batch|no_exchange|frozen]

On the chip, at the cell's own sizes, in one process: for each seed, the
program's first three steps through the cell's ``Trainer`` (as a benchmark
run makes them) against the plain reference, and for each control seed the
control (the reference computed in float8, put in the program's place)
against the reference. With ``--fault`` the program runs with that fault
planted:

- ``half_batch``: the loss is the mean over the first half of each chip's
  rows only;
- ``no_exchange``: the gradient exchange between chips is left out;
- ``frozen``: the optimizer returns the state unchanged.

One JSON line per reading on standard output. The benchmark's own runs
never run this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def plant(fault: str):
    """Break the program's step underneath, before the job is built.
    Returns a function that undoes it."""
    if not fault:
        return lambda: None
    from repro.core import lars, losses
    from repro.train import trainer

    if fault == "half_batch":
        mod, name = losses, "label_smoothing_xent"
        full = losses.label_smoothing_xent

        def broken(logits, labels, *a, **kw):
            n = logits.shape[0] // 2
            return full(logits[:n], labels[:n], *a, **kw)
    elif fault == "no_exchange":
        mod, name = trainer, "sync_tree"

        def broken(grads, grid, cfg=None):
            return grads
    elif fault == "frozen":
        mod, name = lars, "update"

        def broken(params, grads, opt_state, **kw):
            return params, opt_state
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    orig = getattr(mod, name)
    setattr(mod, name, broken)
    return lambda: setattr(mod, name, orig)


def main(argv=None, devices=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import compare, harness

    cell = harness.Cell.load(args.workload, ROOT)
    import jax

    from repro.launch.train import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = devices or jax.devices()[:cell.chips]
    plant(args.fault)
    job = harness.Job(cell, devices)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    out = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        state, prog = job.first_steps(seed, lambda m: None)
        del state
        ref = job.reference(seed)
        rows = [("fault:" + args.fault if args.fault else "program", prog)]
        if seed in controls:
            rows.append(("control", job.reference(seed, quant="fp8")))
        for who, got in rows:
            line = {"workload": cell.name, "seed": seed, "who": who,
                    **compare.readings(got, ref),
                    **compare.worst(got, ref, job.leaf_names),
                    "loss_steps": [float(x) for x in got["loss"]],
                    "ref_loss_steps": [float(x) for x in ref["loss"]],
                    "seconds": round(time.perf_counter() - t, 3)}
            out.append(line)
            print(json.dumps(line), flush=True)
    return out


if __name__ == "__main__":
    main()
