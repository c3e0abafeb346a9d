#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine and print its
result line.

    python3 benchmarks/chip/run.py --workload smollm-360m.m2.local10 \
        --seed 7 --seconds 20 --trace 0

The cells, configurations and metrics are in ``BENCHMARK.json`` at the
root of the checkout.  ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read from a profiler trace of a few
epochs after the window.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``, each number
compared beside its limit); the same numbers close standard error.
Without a TPU, or with fewer chips than the cell needs, it exits 2 before
any work and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the raw .xplane.pb of a --trace 1 run "
                         "to this path")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    # the TPU runtime would log to a fixed /tmp path otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, devices=devices[:cell.chips],
                         keep_trace=args.keep_trace)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
