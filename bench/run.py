"""The chip benchmark: run one cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the TPU chips the
cell asks for (``BENCHMARK.json``). Exits nonzero and prints no result when
JAX finds no TPU or fewer chips than that. The last line of standard output
is one JSON object: ``correct``, ``attempted`` (steps in the window),
``failed`` (steps the program's guard skipped), ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
with ``--trace 1`` a ``breakdown``, and last the ``checks`` that decided
``correct``, each with its limit. Progress, per-step times, set-up parts,
compile counts and the checks go to standard error.

``--keep-trace <dir>`` (with ``--trace 1``) keeps the profiler trace in
``<dir>`` instead of deleting it once reduced: it is how the traces in
``bench/tests/data`` were recorded.
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="write the profiler trace here and keep it")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.Cell.load(args.workload, ROOT)
    import jax

    from repro.launch.train import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs TPU chips, JAX found "
                         f"{devices[0].platform!r}; no result")
    if len(devices) < cell.chips:
        raise SystemExit(f"bench: {args.workload} needs {cell.chips} chips, "
                         f"JAX found {len(devices)}; no result")
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], T_START,
                      keep_trace=args.keep_trace)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
