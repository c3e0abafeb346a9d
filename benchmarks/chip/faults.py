"""Faults planted in the trainer under the harness, to show that the check
rejects them.  Each ``plant_<fault>(fed)`` replaces what the engine runs
for the federation's current size; the harness calls it once the state is
placed.  Used by ``calibrate.py`` on the chip and by the tests on the CPU.

* ``unchanged``: the epoch step returns its state as it came in (only the
  epoch counter moves), with the loss of the real step.
* ``half_batch``: each client's loss covers the first half of its
  sequence positions, the mean taken over those.
* ``no_exchange``: the servers exchange nothing; every epoch's mixing
  matrix is the identity.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _install(fed, step_fn) -> None:
    eng = fed.engine
    eng._steps[eng.topo.num_servers] = eng._jit(step_fn, 0)


def _real_step(fed, loss_fn=None):
    from repro.core import dfl

    eng = fed.engine
    cfg = dataclasses.replace(eng.cfg, topology=eng.topo)
    return dfl.build_dfl_epoch_step(cfg, loss_fn or eng.loss_fn,
                                    eng.optimizer)


def plant_unchanged(fed) -> None:
    real = _real_step(fed)

    def step(state, batches, sched):
        _, metrics = real(state, batches, sched)
        return state._replace(epoch=state.epoch + 1), metrics

    _install(fed, step)


def plant_half_batch(fed) -> None:
    loss_fn = fed.engine.loss_fn

    def half(params, batch, rng):
        tokens = batch["tokens"]
        return loss_fn(params, {"tokens": tokens[:, :tokens.shape[1] // 2]},
                       rng)

    _install(fed, _real_step(fed, half))


@dataclasses.dataclass(frozen=True)
class _Isolated:
    def mixing(self, topo, epoch):
        del epoch
        return np.eye(topo.num_servers)


def plant_no_exchange(fed) -> None:
    fed.engine.topology_schedule = _Isolated()


FAULTS = {"unchanged": plant_unchanged, "half_batch": plant_half_batch,
          "no_exchange": plant_no_exchange}
