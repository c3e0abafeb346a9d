"""Pallas TPU kernels for the system's compute hot-spots (DESIGN.md §6).

Each kernel ships three layers: ``<name>.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jitted layout-adapting wrapper the models call), ``ref.py``
(pure-jnp oracle the sweep tests compare against).  Every kernel entry
point takes ``interpret`` with no default, so each call states its mode:
``ops.py`` passes ``interpret=True`` only when the backend is not a TPU,
and the CPU tests pass it explicitly.
"""
from repro.kernels import ops, ref
from repro.kernels.ops import (consensus_mix_pytree, flash_attention,
                               rmsnorm, ssd_scan)

__all__ = ["ops", "ref", "flash_attention", "ssd_scan",
           "consensus_mix_pytree", "rmsnorm"]
