#!/usr/bin/env python3
"""Run the DFL trainer's main path on a TPU once, and check what it gives.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips, one server per chip

One chip: ``repro.launch.train.train_dynamic`` (the engine path:
``launch/train.py`` -> ``core.engine`` -> ``core.dfl``) trains smollm-360m
at its published widths and depth (32 layers, d_model 960, 361.8M
parameters) from random weights and synthetic token streams, both made from
``--seed``.  The federation is M=2 servers with N=1 client each, per-client
batch 1, sequence 256, T_C=2 local steps and T_S=3 gossip rounds per epoch:
the chip's compiler refuses batch 2 at sequence 512 for this federation.
Checks:

* every epoch's loss is finite, and the last epoch's is below the first's;
* at the initial weights, the loss of client (0, 0)'s first batch on the
  chip agrees with the same call on the host CPU (``LOSS_RTOL``);
* the peak of device memory stays under 16 GB.

Four chips (``--four-chips``, this phase only): M=4 servers, one per chip,
each with its DFL state and batches placed on a ('server',) mesh.  Gossip
runs the int8 physical wire with error feedback, once through the shard_map
collectives and once through the in-graph reference
(``consensus.gossip_scan_wire_bucketed``), with the same seed and placement.
The model keeps smollm-360m's widths at ``FOUR_CHIP_LAYERS`` of its 32
layers, the depth of the benchmark's four-chip cell.  (The shard_map step
used to decode the gathered codes to f32, 16.4 GB per chip at 16 layers;
since it mixes them in their chunk view its compile peaks at 7.3 GB per
chip at 16 layers and 12.6 GB at 32.)  Checks: the two wires'
final server weights agree (``PARAM_ATOL``), their losses are finite, every
chip holds its server's share, each placed step compiled once, and each
chip's bytes in use are within 25% of the mean.

Every phase prints what it measured; the last line of standard output is
``{"ok": true, "device": {...}}`` and is printed only when every check
passed.  Without a TPU the script exits nonzero before any other work.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "smollm-360m"
SHAPE = dict(clients=1, per_client_batch=1, seq_len=256, t_client=2,
             t_server=3)
# SGD step: at the trainer's default 0.05 the full-width loss swings
# (10.79, 9.32, 9.38, 10.65 over 4 epochs on a v5e); 0.01 descends steadily
GAMMA = 0.01
FOUR_CHIP_LAYERS = 8
WIRE = dict(compression="int8", error_feedback=True, wire="physical")
# The chip multiplies f32 matrices in one bf16 pass at default precision
# (8-bit significand, unit roundoff 2**-8 = 3.9e-3); the CPU reference runs
# them in f32.  The mean cross-entropy averages those rounding errors over
# 255 positions, so it agrees well inside one bf16 unit: 1e-3 relative is
# a quarter unit (a v5e measured 1.1e-4).
LOSS_RTOL = 1e-3
# Both wires quantize the same innovations with the same dither, so they
# differ only where the two compiled programs round the local period
# differently; a stochastic-rounding decision that flips then moves one
# coordinate by one int8 step of its chunk's innovation.
PARAM_ATOL = 1e-4
HBM_LIMIT = 16e9
BALANCE = 0.25


def _versions(jax) -> str:
    import jaxlib
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    return (f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
            f"libtpu {libtpu}")


class Checks:
    """Collects named pass/fail results; each is printed as it is made."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"check {name}: {'pass' if ok else 'FAIL'} ({detail})")
        if not ok:
            self.failed.append(name)


def _epoch_clock():
    """A metric sink stamping the host time of each epoch event.  The
    engine emits it after reading the epoch's metrics back, so the stamp
    follows the end of that epoch's program on the device."""
    from repro.obs import Sink

    class EpochClock(Sink):
        def __init__(self):
            self.times = []

        def emit(self, ev) -> None:
            if ev.kind == "epoch":
                self.times.append(time.perf_counter())

    return EpochClock()


def one_chip(jax, args, check: Checks) -> None:
    import numpy as np

    from repro.launch.train import _setup_lm, train_dynamic
    from repro.models import transformer as tf

    servers = 2
    cfg, _, loss_fn, _, pipe = _setup_lm(
        ARCH, False, servers, SHAPE["clients"], SHAPE["t_client"],
        SHAPE["t_server"], "ring", GAMMA, SHAPE["seq_len"],
        SHAPE["per_client_batch"], args.seed, "reference")
    # the weights and the first batch train_dynamic starts from
    params = tf.init_params(jax.random.key(args.seed), cfg)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    print(f"model {ARCH}: {n_params} parameters ({n_params / 1e6:.1f}M), "
          f"{cfg.num_layers} layers, d_model {cfg.d_model}")
    print(f"shape M={servers} N={SHAPE['clients']} "
          f"B={SHAPE['per_client_batch']} S={SHAPE['seq_len']} "
          f"T_C={SHAPE['t_client']} T_S={SHAPE['t_server']}")
    batch = jax.tree.map(lambda x: x[0, 0, 0], pipe.epoch_batches(0))
    chip_loss = float(jax.jit(loss_fn)(params, batch, None)[0])
    cpu = jax.devices("cpu")[0]
    host = jax.device_put(jax.device_get((params, batch)), cpu)
    del params
    with jax.default_matmul_precision("highest"):
        cpu_loss = float(jax.jit(loss_fn)(*host, None)[0])
    del host
    rel = abs(chip_loss - cpu_loss) / abs(cpu_loss)
    print(f"initial loss of client (0,0), first batch: chip {chip_loss!r}, "
          f"cpu {cpu_loss!r}, relative difference {rel!r}")
    check("loss_matches_cpu", rel <= LOSS_RTOL,
          f"{rel:.3e} <= {LOSS_RTOL}: the chip's f32 matmuls run one bf16 "
          f"pass, the CPU's full f32")

    clock = _epoch_clock()
    t0 = time.perf_counter()
    res = train_dynamic(ARCH, smoke=False, servers=servers,
                        epochs=args.epochs, seed=args.seed, gamma=GAMMA,
                        sinks=(clock,), **SHAPE)
    hist = res["history"]
    print(f"first epoch, compile included: {clock.times[0] - t0!r} s")
    for e, (loss, dis) in enumerate(zip(hist["loss"],
                                        hist["disagreement"])):
        line = f"epoch {e}: loss {loss!r} disagreement {dis!r}"
        if e:
            line += (f" seconds {clock.times[e] - clock.times[e - 1]!r} "
                     f"(smoke timing, not a benchmark metric)")
        print(line)
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"peak_bytes_in_use {peak} ({peak / 1e9:.2f} GB)")
    losses = np.asarray(hist["loss"])
    check("losses_finite", bool(np.isfinite(losses).all()
                                and np.isfinite(hist["disagreement"]).all()),
          f"{len(losses)} epochs")
    check("loss_falls", bool(losses[-1] < losses[0]),
          f"{float(losses[0])!r} -> {float(losses[-1])!r}")
    check("peak_hbm", peak < HBM_LIMIT, f"{peak} < {HBM_LIMIT:.0f}")


def four_chips(jax, args, check: Checks) -> None:
    import numpy as np

    from repro.launch.train import train_dynamic

    devices = jax.devices()
    if len(devices) < 4:
        check("four_devices", False, f"{len(devices)} device(s) visible")
        return
    print(f"model {ARCH} widths at {FOUR_CHIP_LAYERS} layers; shape M=4 "
          f"N={SHAPE['clients']} B={SHAPE['per_client_batch']} "
          f"S={SHAPE['seq_len']} T_C={SHAPE['t_client']} "
          f"T_S={SHAPE['t_server']}; wire {WIRE}")
    servers = {}
    for backend in ("shard_map", "einsum"):
        clock = _epoch_clock()
        t0 = time.perf_counter()
        res = train_dynamic(ARCH, smoke=False, servers=4, epochs=args.epochs,
                            seed=args.seed, gamma=GAMMA,
                            consensus_backend=backend,
                            num_layers=FOUR_CHIP_LAYERS, sinks=(clock,),
                            **SHAPE, **WIRE)
        print(f"{backend}: first epoch, compile included: "
              f"{clock.times[0] - t0!r} s; later epochs "
              f"{np.diff(clock.times).tolist()!r} s (smoke timing, not a "
              f"benchmark metric)")
        print(f"{backend}: loss {res['history']['loss']!r}")
        print(f"{backend}: disagreement {res['history']['disagreement']!r}")
        check(f"{backend}_losses_finite",
              bool(np.isfinite(res["history"]["loss"]).all()),
              f"{len(res['history']['loss'])} epochs")
        leaves = jax.tree.leaves(res["state"].client_params)
        placed = all(len(x.sharding.device_set) == 4
                     and x.sharding.spec[0] == "server" for x in leaves)
        check(f"{backend}_one_server_per_chip", placed,
              "every client-parameter leaf sharded over the 4-chip "
              "('server',) mesh")
        counts = res["engine"].compile_counts()
        check(f"{backend}_compiled_once", set(counts.values()) == {1},
              f"dispatch-cache entries per federation size {counts}")
        if backend == "shard_map":
            in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
            mean = sum(in_use) / len(in_use)
            spread = max(abs(b - mean) for b in in_use) / mean
            print(f"bytes_in_use per device {in_use}, mean {mean!r}")
            print("peak_bytes_in_use per device "
                  f"{[d.memory_stats()['peak_bytes_in_use'] for d in devices]}")
            check("bytes_balanced", spread <= BALANCE,
                  f"largest deviation from the mean {spread:.3f} <= "
                  f"{BALANCE}")
        servers[backend] = jax.device_get(
            [x[:, 0] for x in leaves])
        del res, leaves
    diff = max(float(np.abs(a - b).max())
               for a, b in zip(servers["shard_map"], servers["einsum"]))
    equal = sum(int((a == b).sum()) for a, b in
                zip(servers["shard_map"], servers["einsum"]))
    total = sum(a.size for a in servers["shard_map"])
    print(f"shard_map vs in-graph final server weights: max abs difference "
          f"{diff!r}, {equal} of {total} equal")
    check("wires_agree", diff <= PARAM_ATOL, f"{diff:.3e} <= {PARAM_ATOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (one server per "
                         "chip, shard_map wire against the in-graph one)")
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(_versions(jax))
    print(f"device {dev.device_kind!r}, {len(jax.devices())} device(s)")
    print(f"compile cache {enable_compile_cache()}")
    check = Checks()
    (four_chips if args.four_chips else one_chip)(jax, args, check)
    if check.failed:
        print(f"chip_smoke: failed checks {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
