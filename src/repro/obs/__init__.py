"""repro.obs — structured telemetry for the DFL engine.

Three layers, combinable through one ``Observability`` bundle handed to
``DynamicFederationEngine`` / the trainers:

- ``trace.Tracer``            — host-side span recording -> Chrome trace
                                JSON (Perfetto-loadable).
- ``metrics.MetricsHub``      — typed counter/gauge/histogram events
                                fanned out to Memory/JSONL/Console sinks.
- ``monitor.ConvergenceMonitor`` — Theorem-1 / fig-3 derived gauges +
                                watchdog warnings.

Every ``span``, with or without a bundle, is also a
``jax.profiler.TraceAnnotation`` of the span's name: under a running
``jax.profiler`` trace the engine's host phases land on the same clock
as the device's ops (the compiled program names its own phases with
``jax.named_scope``; see docs/observability.md).  With no profiler
running an annotation costs a check of one flag.

The bundle is BITWISE INERT on training numerics: it only reads floats
the engine already computed, and the engine's compiled programs are
byte-identical with ``OBS_OFF`` (the no-op null bundle, the default) or
a full bundle attached — asserted in ``tests/test_obs.py``.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterable, Optional, Sequence

from jax.profiler import TraceAnnotation

from .metrics import (SCHEMA_VERSION, ConsoleSink, JSONLSink, MemorySink,
                      MetricEvent, MetricsHub, Sink, load_jsonl,
                      validate_jsonl)
from .monitor import FIG3_TOLERANCE, ConvergenceMonitor, WatchdogEvent
from .trace import Span, Tracer, validate_chrome_trace

__all__ = [
    "SCHEMA_VERSION", "FIG3_TOLERANCE", "MetricEvent", "MetricsHub",
    "Sink", "MemorySink", "JSONLSink", "ConsoleSink", "ConvergenceMonitor",
    "WatchdogEvent", "Span", "Tracer", "Observability", "OBS_OFF",
    "load_jsonl", "validate_jsonl", "validate_chrome_trace",
]


@contextlib.contextmanager
def _span(name: str, tracer: Optional[Tracer], args: Dict[str, Any]):
    """One host span: a profiler annotation always, and the tracer's own
    record (yielded) when one is attached — ONE code path whether obs is
    on or off."""
    with TraceAnnotation(name):
        if tracer is None:
            yield None
        else:
            with tracer.span(name, **args) as sp:
                yield sp


class Observability:
    """One handle bundling hub + tracer + monitor.

    Everything is optional: ``Observability()`` gives a bare hub with no
    sinks (still inert, still cheap); pass ``tracer=Tracer()`` to record
    spans, ``monitor=True`` to attach a ``ConvergenceMonitor`` over the
    hub.  The engine/trainers call only ``span`` / ``compile_event`` /
    ``observe`` / ``close``."""

    enabled = True

    def __init__(self, hub: Optional[MetricsHub] = None,
                 tracer: Optional[Tracer] = None,
                 monitor: Any = None):
        self.hub = hub if hub is not None else MetricsHub()
        self.tracer = tracer
        if monitor is True:
            monitor = ConvergenceMonitor(self.hub)
        self.monitor: Optional[ConvergenceMonitor] = monitor

    def span(self, name: str, **args: Any):
        return _span(name, self.tracer, args)

    def compile_event(self, cause: str, **args: Any) -> None:
        if self.tracer is not None:
            self.tracer.compile_event(cause, **args)

    def observe(self, epoch: int, record: Dict[str, float], *,
                servers: Optional[Sequence[int]] = None,
                per_link: Any = None,
                screen_rejected: Optional[Iterable[float]] = None) -> None:
        """Fan one epoch's telemetry out: the full record as an ``epoch``
        event, the ``BytesTracker`` per-link byte matrix as labelled
        counters, robust-screen per-server rejection counts as a
        labelled histogram, then the convergence monitor's checks."""
        self.hub.observe_epoch(epoch, record)
        if per_link is not None:
            ids = list(servers) if servers is not None else None
            m = len(per_link)
            for i in range(m):
                for j in range(m):
                    b = float(per_link[i][j])
                    if b > 0:
                        self.hub.counter(
                            "wire_bytes", b, epoch=epoch,
                            dst=ids[i] if ids else i,
                            src=ids[j] if ids else j)
        if screen_rejected is not None:
            vals = [float(v) for v in screen_rejected]
            self.hub.histogram(
                "screen_rejected", vals, epoch=epoch,
                servers=list(servers) if servers is not None
                else list(range(len(vals))))
        if self.monitor is not None:
            self.monitor.observe(epoch, record)

    def close(self) -> None:
        self.hub.close()


class _ObsOff:
    """The null bundle: every hook but ``span``'s profiler annotation is a
    no-op.  The engine's default, so un-instrumented runs pay one
    attribute read and one ``if`` per hook."""

    enabled = False
    hub = None
    tracer = None
    monitor = None

    __slots__ = ()

    def span(self, name: str, **args: Any):
        return _span(name, None, args)

    def compile_event(self, cause: str, **args: Any) -> None:
        pass

    def observe(self, epoch: int, record: Dict[str, float],
                **kw: Any) -> None:
        pass

    def close(self) -> None:
        pass


OBS_OFF = _ObsOff()
