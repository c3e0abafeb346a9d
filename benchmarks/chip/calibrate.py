#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from.  The
benchmark's own runs never run this; it runs on the chip, in one process:

    python3 benchmarks/chip/calibrate.py --workload smollm-360m.m2.local10 \
        --seeds 1001 1012 --control 3 --faults half_batch no_exchange \
        --fault-seeds 3

For every seed in the inclusive range it sets up the cell as a run does,
drives the engine through the checked epochs, frees the trainer and runs
the reference: one ``program`` line with the compared numbers.  Then the
control (the reference in bfloat16, compared with the float32 reference)
on the first ``--control`` seeds, and each planted fault
(``faults.py``) on the first ``--fault-seeds`` seeds.  Each line is a JSON
object with ``kind``, ``seed``, the readings, and the leaf that gave each
largest gap.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs=2, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", nargs="*", default=())
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchmarks.chip import faults, harness

    cell = harness.load_cell(args.workload)
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate.py: needs the cell's TPU chips", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = list(range(args.seeds[0], args.seeds[1] + 1))

    def emit(kind, seed, prog, ref):
        line = {"kind": kind, "seed": seed, **harness.readings(prog, ref)}
        for name, step in (("update", 0), ("change", harness.CAPTURE[-1])):
            gaps = harness.leaf_gaps(prog, ref, step)
            line[f"{name}_leaf"] = max(gaps, key=gaps.get)
        line["loss"], line["ref_loss"] = prog["loss"], ref["loss"]
        print(json.dumps(line), flush=True)

    def program(fed, seed, plant=None):
        state = fed.new_state(seed)
        if plant is not None:
            plant(fed)
        state, prog = harness.first_steps(fed, state, seed,
                                          harness.batches(cell, seed))
        del state
        gc.collect()
        return prog

    fed = harness.Federation(cell.config, cell.traffic)
    refs = {}
    for seed in seeds:
        prog = program(fed, seed)
        refs[seed] = harness.run_reference(cell, fed.weights(seed), seed)
        emit("program", seed, prog, refs[seed])
    for seed in seeds[:args.control]:
        ctrl = harness.run_reference(cell, fed.weights(seed), seed,
                                     dtype=jnp.bfloat16)
        emit("control", seed, ctrl, refs[seed])
    for fault in args.faults:
        fed = harness.Federation(cell.config, cell.traffic)
        for seed in seeds[:args.fault_seeds]:
            emit(fault, seed, program(fed, seed, faults.FAULTS[fault]),
                 refs[seed])
    return 0


if __name__ == "__main__":
    sys.exit(main())
