#!/usr/bin/env python3
"""The epoch's phases from a profiler trace: device time per named scope
of the compiled epoch program, and the engine's host phases.

The trainer names the phases of its compiled epoch program with
``jax.named_scope`` (``SCOPES``; a model plug-in names the further scopes
of its block, ``models/<name>.py`` ``SCOPES``) and opens a
``jax.profiler`` annotation for each phase of its host loop
(``ENGINE_SPANS``).  A device trace names an op by its HLO instruction
only, so ``scope_map`` reads the scopes off the compiled program's HLO
text (the ``op_name`` metadata of each instruction; a fusion carries its
root's), from ``DynamicFederationEngine.epoch_program(...).as_text()``.
Then, per chip, ``scope_ns`` gives each scope's device time inside the
epoch program: the union of the intervals of the scope's ops on the
``XLA Ops`` line and of its collectives in flight on the ``Async XLA
Ops`` line, clipped to the traced window.  An op counts toward every
scope on its path; the program's ops with no scope go under
``unscoped``.  ``host_turnaround_ns`` is the host's time from one
epoch's ``readback`` end to the next epoch's ``dispatch`` start, and
``idle_by_span`` puts the chip's idle time down to the innermost host
span it fell under.  ``trace_context`` gives the benchmark's metric
readers the scopes' times and the host's spans of a traced run.

Run as a script it measures one cell of ``BENCHMARK.json`` on the chips
of this machine: the harness's set-up and a window of ``--seconds``
untraced epochs, then ``harness.TRACE_EPOCHS`` epochs under the
profiler, compiling afresh (a program from the persistent compile cache
would carry the scopes of the compile that filled it: the cache's key
leaves metadata out).  The last line of standard output is one JSON
object: the phase times per epoch on the slowest chip (ms), the host
turnaround, the compiled program's ``memory_analysis()``, the share of
the program's device time each scope covers, the largest unscoped ops,
the chip's idle time by host span, and what tracing costs::

    python3 benchmarks/chip/phases.py --workload smollm-360m.m2-mean.local10 \\
        --seed 7 --seconds 20
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import trace_reduce  # noqa: E402

# the epoch program's named scopes (core/dfl.py, models/transformer.py,
# core/consensus.py)
SCOPES = ("local_period", "embed", "attention", "mlp", "lm_head",
          "sgd_update", "aggregate", "gossip_period", "wire_pack",
          "wire_encode", "wire_gather", "wire_decode_mix", "epoch_metrics",
          "broadcast")
UNSCOPED = "unscoped"
PROGRAM = "program"       # the busy time of all the program's ops
# the engine's host spans (core/engine.py run_epoch), and the harness's
ENGINE_SPANS = trace_reduce.ENGINE_SPANS
HOST_SPANS = trace_reduce.HOST_SPANS
# per-epoch metric -> the scope it reads
SCOPE_METRICS = {"local_ms": "local_period", "sgd_update_ms": "sgd_update",
                 "attention_ms": "attention", "mlp_ms": "mlp",
                 "lm_head_ms": "lm_head", "gossip_ms": "gossip_period",
                 "encode_ms": "wire_encode",
                 "decode_mix_ms": "wire_decode_mix"}

_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"\(%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def path_scopes(op_name: str, scopes: Sequence[str] = SCOPES
                ) -> Tuple[str, ...]:
    """The scopes of ``scopes`` on an ``op_name`` metadata path, outermost
    first; autodiff's wrappers (``transpose(jvp(attention))``) are
    stripped."""
    out = []
    for seg in op_name.split("/"):
        m = _WRAPPED.match(seg)
        while m:
            seg = m.group(1)
            m = _WRAPPED.match(seg)
        if seg in scopes and seg not in out:
            out.append(seg)
    return tuple(out)


def scope_map(hlo_text: str, scopes: Sequence[str] = SCOPES
              ) -> Dict[str, Tuple[str, ...]]:
    """Instruction name -> the scopes of ``scopes`` on its ``op_name``
    path, for every instruction of the HLO text.  The compiler leaves
    some instructions without metadata: a fusion then takes the path of
    the last instruction of its fused computation that has one (the one
    nearest the root), and any other instruction (a layout copy, a
    ``get-tuple-element``) the path of its first operand; a parameter has
    none, and maps to no scope."""
    paths: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    operand: Dict[str, str] = {}
    last: Dict[str, str] = {}          # computation -> last op_name in it
    comp = None
    for line in hlo_text.splitlines():
        m = _NAME.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                comp = c.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        paths[name] = op.group(1) if op else None
        if op:
            if comp is not None:
                last[comp] = op.group(1)
            continue
        callee = _CALLS.search(line)
        if callee:
            calls[name] = callee.group(1)
        else:
            arg = _OPERAND.search(line, m.end())
            if arg:
                operand[name] = arg.group(1)

    def path(name: str) -> Optional[str]:
        seen = set()
        while paths.get(name) is None and name not in seen:
            seen.add(name)
            if name in calls:
                return last.get(calls[name])
            if name not in operand:
                return None
            name = operand[name]
        return paths.get(name)

    out = {}
    for name in paths:
        p = path(name)
        out[name] = path_scopes(p, scopes) if p else ()
    return out


def load_host(path: str) -> List[Tuple[str, float, float]]:
    """The host's spans of ``HOST_SPANS`` in a raw trace, sorted by
    start."""
    return trace_reduce.load(path).host


def window(trace: trace_reduce.Trace) -> Optional[Tuple[float, float]]:
    """The traced window as ``trace_reduce.reduce`` takes it: from the
    first ``engine`` span's start to the last one's end."""
    engine = [(s, e) for n, s, e in trace.host if n == "engine"]
    if not engine:
        return None
    return engine[0][0], max(e for _, e in engine)


def scope_ns(trace: trace_reduce.Trace,
             red: Dict[int, trace_reduce.ChipReduction],
             smap: Dict[str, Tuple[str, ...]]) -> Dict[int, Dict[str, float]]:
    """Per chip, each scope's device time (ns) inside the epoch program in
    the window; the program's ``unscoped`` time, that of its ops with no
    scope on the ``XLA Ops`` line; and ``program``, the busy time of all
    its ops on that line.  On the async line only collectives count, as
    in ``collective_ms``: a copy in flight there runs beside other ops.  Ops the HLO text
    does not name (another program's) and control flow (whose interval
    encloses its body's ops) are left out."""
    win = window(trace)
    out: Dict[int, Dict[str, float]] = {}
    if win is None:
        return out
    lo, hi = win
    for idx, chip in trace.chips.items():
        r = red.get(idx)
        if r is None or r.step_module is None:
            continue
        prog = trace_reduce.union(trace_reduce.clip(
            [(s, e) for n, s, e in chip.modules if n == r.step_module],
            lo, hi))
        spans: Dict[str, List[Tuple[float, float]]] = {}
        for line, events in ((0, chip.ops), (1, chip.async_ops)):
            for name, s, e in events:
                if (name.startswith(trace_reduce.CONTROL_MARK)
                        or name not in smap or (line and not (
                            smap[name]
                            and trace_reduce.COLLECTIVE.search(name)))):
                    continue
                for sc in smap[name] or (UNSCOPED,):
                    spans.setdefault(sc, []).append((s, e))
                if not line:
                    spans.setdefault(PROGRAM, []).append((s, e))
        out[idx] = {}
        for sc, iv in spans.items():
            merged = trace_reduce.union(trace_reduce.clip(iv, lo, hi))
            t = sum(trace_reduce.covered(prog, s, e) for s, e in merged)
            if t > 0:
                out[idx][sc] = t
    return out


def per_epoch_ms(red: Dict[int, trace_reduce.ChipReduction],
                 scopes: Dict[int, Dict[str, float]], scope: str
                 ) -> Optional[float]:
    """A scope's device time per epoch on the chip where it is largest, in
    ms; None where the scope ran nothing."""
    per = [scopes[i][scope] / red[i].steps for i in scopes
           if red[i].steps and scopes[i].get(scope)]
    return max(per) * 1e-6 if per else None


def scope_ms(red: Dict[int, trace_reduce.ChipReduction],
             scopes: Dict[int, Dict[str, float]], names: Sequence[str]
             ) -> Dict[str, float]:
    """``per_epoch_ms`` of each scope of ``names`` that ran."""
    out = {}
    for sc in names:
        ms = per_epoch_ms(red, scopes, sc)
        if ms is not None:
            out[sc] = ms
    return out


def trace_context(trace: trace_reduce.Trace,
                  red: Dict[int, trace_reduce.ChipReduction],
                  hlo_text: str, extra: Sequence[str] = ()
                  ) -> Dict[str, object]:
    """What the metric readers get of a traced window beside its
    reduction: ``scope_ms``, each scope's device time per epoch on the
    slowest chip over ``SCOPES`` and the model's ``extra`` scopes, from
    the epoch program's HLO text; ``host``, the host's spans."""
    names = tuple(SCOPES) + tuple(s for s in extra if s not in SCOPES)
    scopes = scope_ns(trace, red, scope_map(hlo_text, names))
    return {"scope_ms": scope_ms(red, scopes, names), "host": trace.host}


def host_turnaround_ns(host) -> Optional[float]:
    """Mean over consecutive epochs of (next ``dispatch`` start − this
    ``readback`` end)."""
    disp = [s for n, s, _ in host if n == "dispatch"]
    back = [e for n, _, e in host if n == "readback"]
    gaps = [d - b for b, d in zip(back, disp[1:])]
    return sum(gaps) / len(gaps) if gaps else None


def idle_by_span(host, idle) -> Dict[str, float]:
    """Device-idle time (ns) by the innermost host span the host was in:
    each idle interval is cut at the spans' edges, and each piece goes to
    the shortest span that covers it (``host`` where none does)."""
    out: Dict[str, float] = {}
    for lo, hi in idle:
        inside = [(n, s, e) for n, s, e in host if e > lo and s < hi]
        edges = sorted({lo, hi} | {t for _, s, e in inside for t in (s, e)
                                   if lo < t < hi})
        for a, b in zip(edges, edges[1:]):
            cover = [(e - s, n) for n, s, e in inside if s <= a and b <= e]
            name = min(cover)[1] if cover else "host"
            out[name] = out.get(name, 0.0) + b - a
    return out


def unscoped_ops(red, smap, top: int = 10) -> List[list]:
    """The ops of the epoch program with no scope that took longest (mean
    over chips, seconds in the window)."""
    ops: Dict[str, float] = {}
    for r in red.values():
        for n, ns in r.op_ns.items():
            if n in smap and not smap[n]:
                ops[n] = ops.get(n, 0.0) + ns / len(red)
    return [[n, ns * 1e-9] for n, ns in
            sorted(ops.items(), key=lambda kv: -kv[1])[:top]]


def annotation_ns(reps: int = 100_000) -> float:
    """Host cost of entering and leaving one ``TraceAnnotation`` with no
    profiler running, in ns."""
    import jax

    t0 = time.perf_counter_ns()
    for _ in range(reps):
        with jax.profiler.TraceAnnotation("epoch"):
            pass
    return (time.perf_counter_ns() - t0) / reps


def reduce_phases(trace: trace_reduce.Trace, host, hlo_text: str
                  ) -> Dict[str, object]:
    """The phase numbers of a traced window: ``trace`` from
    ``trace_reduce.load``, ``host`` from ``load_host``, the epoch
    program's HLO text; empty where the trace holds no chip."""
    red = trace_reduce.reduce(trace)
    if not red:
        return {}
    smap = scope_map(hlo_text)
    scopes = scope_ns(trace, red, smap)
    ms = scope_ms(red, scopes, SCOPES)
    out: Dict[str, object] = {
        k: ms.get(sc) for k, sc in SCOPE_METRICS.items()}
    turn = host_turnaround_ns(host)
    out["host_turnaround_ms"] = None if turn is None else turn * 1e-6
    slow = max(red, key=lambda i: red[i].step_ns)
    step = red[slow].step_ns
    out["scope_share"] = {sc: t / step for sc, t in
                          sorted(scopes.get(slow, {}).items())}
    # every scoped op sits in a top-level scope: their share of the
    # program's busy time (the module's span also holds the bubbles
    # between its ops)
    mine = scopes.get(slow, {})
    out["top_share"] = 1.0 - mine.get(UNSCOPED, 0.0) / max(
        mine.get(PROGRAM, 0.0), 1.0)
    out["step_device_ms"] = step / red[slow].steps * 1e-6
    out["unscoped_ops"] = unscoped_ops(red, smap)
    idlest = min(red.values(), key=lambda r: r.busy_ns)
    out["idle_ms_by_span"] = {
        n: ns / idlest.steps * 1e-6 for n, ns in
        sorted(idle_by_span(host, idlest.idle).items(),
               key=lambda kv: -kv[1])}
    return out


def measure(cell, seed: int, seconds: float, devices,
            keep: Optional[str] = None) -> Dict[str, object]:
    """Set-up and window as ``harness.run``, then traced epochs; the phase
    numbers, the epoch program's memory analysis and tracing's cost.
    ``keep``: a path prefix for the raw trace (``.xplane.pb``) and the
    program's HLO text (``.hlo.txt``)."""
    from benchmarks.chip import harness

    fed = harness.Federation(cell.config, cell.traffic)
    batch_fn = harness.batches(cell, seed)
    state = fed.new_state(seed)
    state, _ = harness.first_steps(fed, state, seed, batch_fn)
    setup_s = time.perf_counter() - T_START
    state, records, elapsed = harness.window(
        fed, state, harness.CHECK_STEPS, batch_fn, seconds)
    epoch0 = harness.CHECK_STEPS + len(records)
    state, traced, trace = harness.traced_epochs(
        fed, state, epoch0, batch_fn, keep and keep + ".xplane.pb")
    host = trace.host
    program = fed.engine.epoch_program(
        state, epoch0 + len(traced), batch_fn)
    hlo = program.as_text()
    if keep:
        pathlib.Path(keep + ".hlo.txt").write_text(hlo)
    mem = program.memory_analysis()
    out = reduce_phases(trace, host, hlo)
    out["step_temp_gb"] = mem.temp_size_in_bytes / 1e9
    out["memory_analysis_gb"] = {
        k: getattr(mem, k) / 1e9 for k in
        ("argument_size_in_bytes", "output_size_in_bytes",
         "alias_size_in_bytes", "temp_size_in_bytes")}
    out["peak_bytes_in_use_gb"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices) / 1e9
    engine = [e - s for n, s, e in trace.host if n == "engine"]
    per_epoch = [n for n, _, _ in host if n in ENGINE_SPANS]
    out["tracing"] = {
        "window_epoch_ms": elapsed / len(records) * 1e3,
        "traced_epoch_ms": sum(engine) / len(engine) * 1e-6,
        "spans_per_epoch": len(per_epoch) / len(engine),
        "annotation_off_ns": annotation_ns(),
    }
    out["setup_s"] = setup_s
    out["epochs"] = {"window": len(records), "traced": len(traced)}
    dev = devices[0]
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--keep", default=None,
                    help="path prefix: also keep the raw trace and the "
                         "epoch program's HLO text")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"phases.py: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    # compile afresh: the persistent cache's key leaves the ops' metadata
    # out, so a cached program carries the scope names of whichever
    # compile filled the cache, not this code's
    jax.config.update("jax_enable_compilation_cache", False)
    out = measure(cell, args.seed, args.seconds, devices[:cell.chips],
                  args.keep)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
