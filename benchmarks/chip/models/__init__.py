"""Model plug-ins: what the benchmark knows of one model's block.

A configuration file (``configs/<config>.json``) names its plug-in with
the key ``"reference_model"``; a file without the key takes ``llama``.
``load`` reads ``<models dir>/<name>.py`` from its file path, as the
harness reads the metric readers, so a plug-in arrives as a new file and
nothing here or in the harness is edited for it.

A plug-in module provides:

* ``arch_config(config, base)``: the trainer's ``ArchConfig`` for the
  file's sizes.  ``base`` is the trainer's registered configuration for
  ``config["program_arch"]``, which the harness looks up; the plug-in
  returns it with the file's sizes (``dataclasses.replace``), and raises
  ``ValueError`` for a block that its reference does not compute.
* ``apply_options(config)``: the keyword arguments of the trainer's
  ``ApplyOptions`` that the harness builds the loss with.
* ``init_weights(key, config)``: the seeded float32 weights in the
  trainer's parameter layout (the harness checks the layout against the
  trainer's own ``init_params``); jitted whole, one program on the device.
* ``loss(w, tokens, config)``: mean next-token cross-entropy of
  ``tokens`` (b, s), plain, in the weights' dtype (float32 under
  ``highest`` matmul precision for the reference, bfloat16 for the
  control).
* ``train_flops_per_token(config, seq_len)``: the model FLOPs of training
  one token, by ``flops.py``'s convention.
* ``SCOPES``: the ``jax.named_scope`` names that the trainer's program
  opens for this model beyond ``phases.SCOPES``; the harness measures
  each one's device time into the readers' ``ctx["scope_ms"]``.

The configuration file also gives ``vocab_size``: the traffic draws token
ids below it.

A plug-in imports nothing of the trainer (``repro``): the reference is
written from the published description alone, and takes nothing that the
program has made.  It may import JAX, NumPy and ``benchmarks.chip.flops``.

A new configuration ships, all as new files: its plug-in (where no
existing one computes its block); its configuration file, with
``reference_model``; a traffic file under ``traffic/`` and a limits file
under ``limits/`` for each cell; a reader under ``metrics/`` for each new
per-layer metric (a scope's reader is a line over ``ctx["scope_ms"]``);
and its ``configs``, ``workloads`` and ``per_layer`` entries in
``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import pathlib
from types import ModuleType
from typing import Any, Dict

HERE = pathlib.Path(__file__).resolve().parent
DEFAULT = "llama"


def load(config: Dict[str, Any], where: pathlib.Path = HERE) -> ModuleType:
    """The plug-in that ``config`` names, loaded from ``where``."""
    name = config.get("reference_model", DEFAULT)
    path = where / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model plug-in {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_model_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
