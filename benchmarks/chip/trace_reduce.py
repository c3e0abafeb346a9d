"""Reduce a profiler trace of the measured window to device metrics.

``load(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain lists: per chip the ``XLA Ops`` and ``XLA Modules`` events, and
the host's ``TraceAnnotation`` spans: those that the harness opens
(``engine`` around each ``run_epoch`` call, ``input`` around each batch)
and the engine's own phases inside ``run_epoch``.  ``reduce``
then gives, per chip: busy time (the union of op intervals inside the
window), idle share, the epoch program's device time, the idle gap between
one epoch program and the next, collective time, and a breakdown of the
ops that took longest and of the longest idle gaps by what the host was
doing.  The window runs from the first ``engine`` span's start to the last
one's end.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

ANNOTATIONS = ("engine", "input")
# the engine's host spans (core/engine.py run_epoch)
ENGINE_SPANS = ("epoch", "fault-surgery", "schedule", "batch", "dispatch",
                "readback", "host-aggregation")
HOST_SPANS = ANNOTATIONS + ENGINE_SPANS
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all", re.I)

# an op that encloses others (a loop, a branch, a call) is busy time, but
# not an op of its own in the breakdown
CONTROL = re.compile(r"(while|conditional|call)(\.\d+)?$")
CONTROL_MARK = "control:"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Chip:
    ops: List[Tuple[str, float, float]]        # (name, start ns, end ns)
    modules: List[Tuple[str, float, float]]
    async_ops: List[Tuple[str, float, float]]  # copies, collectives in flight


@dataclasses.dataclass
class Trace:
    chips: Dict[int, Chip]
    host: List[Tuple[str, float, float]]       # annotation spans


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips: Dict[int, Chip] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = Chip([], [], [])
            for line in plane.lines:
                dest = {"XLA Ops": chip.ops, "XLA Modules": chip.modules,
                        "Async XLA Ops": chip.async_ops}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append((op_name(ev.name), ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
            chips[int(m.group(1))] = chip
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return Trace(chips, sorted(host, key=lambda e: e[1]))


def op_name(text: str) -> str:
    """An op event is named by its HLO instruction text; keep the
    instruction's name (``fusion.585``), marked where it is control flow
    that encloses other ops (XLA names an instruction by its opcode)."""
    name = text.partition(" = ")[0].lstrip("%")
    if CONTROL.match(name):
        return CONTROL_MARK + name
    return name


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in clip(merged, lo, hi))


def _step_module(chip: Chip, lo: float, hi: float) -> Optional[str]:
    """The module with the most device time in the window: the epoch
    program."""
    total: Dict[str, float] = {}
    for name, s, e in chip.modules:
        if e > lo and s < hi:
            total[name] = total.get(name, 0.0) + min(e, hi) - max(s, lo)
    return max(total, key=total.get) if total else None


def _annotation_at(host, lo: float, hi: float) -> str:
    """The innermost host span overlapping [lo, hi] most, or ``host``."""
    best, best_key = "host", (0.0, 0.0)
    for name, s, e in host:
        ov = min(e, hi) - max(s, lo)
        if ov <= 0:
            continue
        key = (ov, -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


@dataclasses.dataclass
class ChipReduction:
    busy_ns: float
    window_ns: float
    step_module: Optional[str]
    step_ns: float             # device time of the epoch program, summed
    steps: int                 # epoch programs that started in the window
    gaps_ns: List[float]       # idle time between consecutive epoch programs
    collective_ns: float
    op_ns: Dict[str, float]
    idle: List[Interval]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns


def reduce(trace: Trace) -> Dict[int, ChipReduction]:
    engine = [(s, e) for n, s, e in trace.host if n == "engine"]
    if not engine or not trace.chips:
        return {}
    lo, hi = engine[0][0], max(e for _, e in engine)
    out = {}
    for idx, chip in sorted(trace.chips.items()):
        merged = union(clip([(s, e) for _, s, e in chip.ops], lo, hi))
        busy = sum(e - s for s, e in merged)
        name = _step_module(chip, lo, hi)
        steps = sorted((s, e) for n, s, e in chip.modules
                       if n == name and lo <= s < hi)
        gaps = [max(b - a, 0.0) - covered(merged, a, b)
                for (_, a), (b, _) in zip(steps, steps[1:])]
        op_ns: Dict[str, float] = {}
        for n, s, e in chip.ops:
            dur = min(e, hi) - max(s, lo)
            if dur > 0 and not n.startswith(CONTROL_MARK):
                op_ns[n] = op_ns.get(n, 0.0) + dur
        coll = sum(e - s for s, e in union(clip(
            [(s, e) for n, s, e in chip.ops + chip.async_ops
             if COLLECTIVE.search(n)], lo, hi)))
        idle, t = [], lo
        for s, e in merged:
            if s > t:
                idle.append((t, s))
            t = e
        if t < hi:
            idle.append((t, hi))
        out[idx] = ChipReduction(
            busy, hi - lo, name,
            sum(min(e, hi) - s for s, e in steps), len(steps), gaps,
            coll, op_ns, idle)
    return out


def breakdown(trace: Trace, red: Dict[int, ChipReduction], top: int = 10
              ) -> Dict[str, list]:
    """Device ops by time (mean over chips) and the longest idle gaps of
    the idlest chip by the host span they fall under, in seconds."""
    ops: Dict[str, float] = {}
    for r in red.values():
        for n, ns in r.op_ns.items():
            ops[n] = ops.get(n, 0.0) + ns / len(red)
    worst = min(red.values(), key=lambda r: r.busy_ns)
    gaps = sorted(worst.idle, key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, ns * 1e-9] for n, ns in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_annotation_at(trace.host, s, e), (e - s) * 1e-9]
                      for s, e in gaps],
    }
