"""repro.obs: the telemetry layer's contracts — JSONL schema round-trip,
span nesting/ordering invariants under an injected deterministic clock,
Chrome trace-event validity, sink fan-out, watchdog rules on seeded
pathologies, and the load-bearing guarantee: a fully-instrumented engine
run is BITWISE identical to an uninstrumented one."""

import io
import json
import math

import jax
import jax.numpy as jnp
import pytest

from repro.core import (FaultEvent, FaultSchedule, FLTopology,
                        ParticipationSchedule, TopologySchedule,
                        init_dfl_state, make_engine)
from repro.data import RegressionSpec, make_regression_task
from repro.obs import (OBS_OFF, SCHEMA_VERSION, ConsoleSink,
                       ConvergenceMonitor, JSONLSink, MemorySink,
                       MetricEvent, MetricsHub, Observability, Tracer,
                       load_jsonl, validate_chrome_trace, validate_jsonl)
from repro.optim import sgd

# the engine's host phases, in the order run_epoch opens them
ENGINE_SPANS = ("epoch", "fault-surgery", "schedule", "batch", "dispatch",
                "readback", "host-aggregation")

# ---------------------------------------------------------------------------
# tracer: spans, nesting, Chrome export
# ---------------------------------------------------------------------------


def _fake_clock():
    """Deterministic injectable clock: 0, 10, 20, ... nanoseconds."""
    t = {"now": -10}

    def clock():
        t["now"] += 10
        return t["now"]
    return clock


def test_span_nesting_and_ordering_invariants():
    tr = Tracer(clock=_fake_clock())
    with tr.span("epoch", epoch=0) as outer:
        with tr.span("dispatch"):
            pass
        with tr.span("readback"):
            pass
    # children appended at EXIT, before the outer span closes
    names = [s.name for s in tr.spans]
    assert names == ["dispatch", "readback", "epoch"]
    local, gossip, epoch = tr.spans
    assert epoch is outer
    # time containment + sibling ordering under the monotonic clock
    assert epoch.encloses(local) and epoch.encloses(gossip)
    assert local.t1_ns <= gossip.t0_ns
    assert all(s.duration_ns >= 0 for s in tr.spans)
    # nesting metadata
    assert epoch.depth == 0 and local.depth == 1 and gossip.depth == 1
    assert local.parent is epoch and gossip.parent is epoch
    assert epoch.args == {"epoch": 0}


def test_chrome_trace_export_is_valid_and_complete():
    tr = Tracer(clock=_fake_clock())
    with tr.span("epoch", epoch=3):
        with tr.span("fault-surgery"):
            pass
    tr.compile_event("first_trace", m=4)
    doc = tr.to_chrome()
    events = validate_chrome_trace(doc)
    assert doc["displayTimeUnit"] == "ms"
    xs = [e for e in events if e["ph"] == "X"]
    insts = [e for e in events if e["ph"] == "i"]
    assert {e["name"] for e in xs} == {"epoch", "fault-surgery"}
    assert [e["name"] for e in insts] == ["compile"]
    assert insts[0]["args"] == {"cause": "first_trace", "m": 4}
    # X events are time-sorted with microsecond ts/dur
    assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)
    # non-JSON-serialisable args are stringified, never dropped
    with tr.span("epoch", arr=jnp.zeros(2)):
        pass
    json.dumps(tr.to_chrome())


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"events": []})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "Z",
                                                "ts": 0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "X",
                                                "ts": 0}]})  # no dur


def test_save_chrome_round_trips(tmp_path):
    tr = Tracer(clock=_fake_clock())
    with tr.span("epoch"):
        pass
    p = tmp_path / "trace.json"
    tr.save_chrome(str(p))
    validate_chrome_trace(json.loads(p.read_text()))


# ---------------------------------------------------------------------------
# hub + sinks: fan-out, JSONL schema round-trip
# ---------------------------------------------------------------------------


def test_sink_fanout_every_sink_sees_every_event(capsys):
    mem1, mem2 = MemorySink(), MemorySink()
    buf = io.StringIO()
    hub = MetricsHub([mem1, ConsoleSink()])
    hub.add_sink(mem2)
    hub.add_sink(JSONLSink(buf))
    hub.observe_epoch(0, {"loss": 1.5, "disagreement": 2e-4})
    hub.counter("wire_bytes", 100.0, epoch=0, src=1, dst=0)
    hub.warning("nan-loss", "loss is non-finite", epoch=0)
    hub.close()
    for mem in (mem1, mem2):
        assert mem.history() == {"loss": [1.5], "disagreement": [2e-4]}
        assert mem.totals() == {"wire_bytes": 100.0}
        assert [w.name for w in mem.warnings()] == ["nan-loss"]
    out = capsys.readouterr().out
    assert "epoch    0" in out and "loss=1.5000" in out
    assert "[obs:warn] nan-loss" in out
    lines = [json.loads(l) for l in buf.getvalue().splitlines()]
    assert lines[0]["kind"] == "meta"
    assert [l["kind"] for l in lines[1:]] == ["epoch", "counter", "warning"]


def test_console_sink_respects_log_every(capsys):
    hub = MetricsHub([ConsoleSink(log_every=3)])
    for e in range(7):
        hub.observe_epoch(e, {"loss": float(e)})
    out = capsys.readouterr().out
    printed = [l for l in out.splitlines() if l.startswith("epoch")]
    assert len(printed) == 3          # epochs 0, 3, 6


def test_jsonl_schema_round_trip(tmp_path):
    p = tmp_path / "telemetry.jsonl"
    hub = MetricsHub([JSONLSink(str(p), run_info={"driver": "test"})])
    hub.observe_epoch(0, {"loss": 2.0, "sigma_prod": 0.5})
    hub.gauge("tolerance_gap", 3.5, epoch=0)
    hub.histogram("screen_rejected", [0.0, 2.0, 1.0], epoch=0,
                  servers=[0, 1, 2])
    hub.counter("wire_bytes", 42.0, epoch=0, src=2, dst=1)
    hub.close()
    records = load_jsonl(str(p))
    assert records[0] == {"kind": "meta", "schema": SCHEMA_VERSION,
                          "unix_time": records[0]["unix_time"],
                          "run": {"driver": "test"}}
    events = validate_jsonl(records)
    by_kind = {e["kind"]: e for e in events}
    assert by_kind["epoch"]["value"] == {"loss": 2.0, "sigma_prod": 0.5}
    assert by_kind["gauge"] == {"kind": "gauge", "name": "tolerance_gap",
                                "value": 3.5, "epoch": 0}
    assert by_kind["histogram"]["value"] == [0.0, 2.0, 1.0]
    assert by_kind["histogram"]["labels"] == {"servers": [0, 1, 2]}
    assert by_kind["counter"]["labels"] == {"src": 2, "dst": 1}


def test_validate_jsonl_rejects_bad_streams():
    meta = {"kind": "meta", "schema": SCHEMA_VERSION}
    with pytest.raises(ValueError):
        validate_jsonl([])
    with pytest.raises(ValueError):
        validate_jsonl([{"kind": "epoch", "name": "epoch", "value": {}}])
    with pytest.raises(ValueError):
        validate_jsonl([{"kind": "meta", "schema": SCHEMA_VERSION + 1}])
    with pytest.raises(ValueError):
        validate_jsonl([meta, {"kind": "spam", "name": "x", "value": 1}])
    with pytest.raises(ValueError):
        validate_jsonl([meta, {"kind": "gauge", "name": "g", "value": [1]}])
    with pytest.raises(ValueError):
        validate_jsonl([meta, {"kind": "histogram", "name": "h",
                               "value": 1.0}])


# ---------------------------------------------------------------------------
# convergence monitor: derived gauges + watchdog rules
# ---------------------------------------------------------------------------


def test_monitor_gauges_track_paper_quantities():
    mem = MemorySink()
    hub = MetricsHub([mem])
    events = []
    hub.gauge = lambda name, value, *, epoch=None, **kw: \
        events.append((name, value, epoch))  # capture without a sink walk
    mon = ConvergenceMonitor(hub)
    mon.observe(0, {"loss": 1.0, "disagreement": 0.5, "sigma_prod": 0.8})
    mon.observe(1, {"loss": 0.9, "disagreement": 0.1, "sigma_prod": 0.4})
    gaps = [v for n, v, _ in events if n == "tolerance_gap"]
    bounds = [v for n, v, _ in events if n == "contraction_bound"]
    assert gaps == [0.5 / 1e-3, 0.1 / 1e-3]
    # d0 is the FIRST disagreement; bound contracts with sigma_prod
    assert bounds == [0.8 * 0.5, 0.4 * 0.5]


def test_watchdog_nan_loss_fires_once():
    mem = MemorySink()
    mon = ConvergenceMonitor(MetricsHub([mem]))
    mon.observe(0, {"loss": 1.0, "disagreement": 1e-4})
    assert mon.events == []
    mon.observe(1, {"loss": float("nan"), "disagreement": 1e-4})
    mon.observe(2, {"loss": float("inf"), "disagreement": 1e-4})
    assert [e.rule for e in mon.events] == ["nan-loss"]
    assert mon.events[0].epoch == 1
    assert [w.name for w in mem.warnings()] == ["nan-loss"]


def test_watchdog_disagreement_divergence():
    mon = ConvergenceMonitor(MetricsHub([MemorySink()]),
                             divergence_window=3)
    dis = [1e-4, 1e-4, 1e-4, 1e-4, 5e-2]     # 500x jump over the window
    for e, d in enumerate(dis):
        mon.observe(e, {"loss": 1.0, "disagreement": d})
    assert [e.rule for e in mon.events] == ["disagreement-divergence"]
    assert mon.events[0].value == pytest.approx(5e-2)


def test_watchdog_wire_ratio_regression():
    mon = ConvergenceMonitor(MetricsHub([MemorySink()]))
    mon.observe(0, {"loss": 1.0, "wire_ratio": 4.0})
    mon.observe(1, {"loss": 1.0, "wire_ratio": 3.5})   # mild dip: no fire
    assert mon.events == []
    mon.observe(2, {"loss": 1.0, "wire_ratio": 1.0})   # collapsed
    assert [e.rule for e in mon.events] == ["wire-ratio-regression"]


# ---------------------------------------------------------------------------
# the Observability bundle + the bitwise-inertness contract
# ---------------------------------------------------------------------------


def test_obs_off_is_a_complete_null_object():
    assert OBS_OFF.enabled is False
    with OBS_OFF.span("epoch", epoch=0) as sp:
        assert sp is None
    OBS_OFF.compile_event("first_trace")
    OBS_OFF.observe(0, {"loss": 1.0}, servers=(0,), per_link=None)
    OBS_OFF.close()


def test_observability_labels_per_link_and_screen(tmp_path):
    mem = MemorySink()
    obs = Observability(hub=MetricsHub([mem]), tracer=Tracer(),
                        monitor=True)
    per_link = [[0.0, 7.0], [3.0, 0.0]]
    obs.observe(0, {"loss": 1.0, "disagreement": 1e-4},
                servers=(0, 2),              # dense rows -> original ids
                per_link=per_link, screen_rejected=[1.0, 0.0])
    obs.close()
    assert mem.totals() == {"wire_bytes": 10.0}
    assert mem.history()["loss"] == [1.0]
    assert obs.monitor is not None and obs.monitor.events == []


def _small_engine(obs=None, faults=None):
    topo = FLTopology(num_servers=3, clients_per_server=2, t_client=2,
                      t_server=3, graph_kind="ring")
    task = make_regression_task(topo, RegressionSpec(heterogeneity=0.5),
                                seed=0)
    opt = sgd(1e-3)
    eng = make_engine(topo, task["loss_fn"], opt,
                      participation=ParticipationSchedule(
                          kind="bernoulli", rate=0.7, seed=3),
                      topology_schedule=TopologySchedule(
                          kind="edge_drop", drop_prob=0.3, seed=4),
                      faults=faults, obs=obs)
    state = init_dfl_state(eng.cfg, jnp.zeros((2,)), opt, jax.random.key(0))
    return eng, state, task["batch_fn"]


def test_engine_history_bitwise_identical_with_obs_on():
    """The load-bearing contract: attaching the FULL obs stack (hub +
    sinks + tracer + monitor) must not change a single bit of any
    training metric."""
    faults = FaultSchedule((FaultEvent(2, "drop", 1),
                            FaultEvent(4, "rejoin", 1)))
    epochs = 6

    def run(obs):
        eng, state, batch_fn = _small_engine(obs=obs, faults=faults)
        hist = {}
        for e in range(epochs):
            state, rec = eng.run_epoch(state, e, batch_fn)
            for k, v in rec.items():
                hist.setdefault(k, []).append(v)
        return hist

    plain = run(None)                          # defaults to OBS_OFF
    obs = Observability(hub=MetricsHub([MemorySink()]), tracer=Tracer(),
                        monitor=True)
    traced = run(obs)
    assert set(plain) == set(traced)
    for k in plain:
        for a, b in zip(plain[k], traced[k]):
            assert a == b or (math.isnan(a) and math.isnan(b)), \
                f"obs changed {k}: {a!r} != {b!r}"


def test_engine_emits_spans_and_compile_events():
    faults = FaultSchedule((FaultEvent(2, "drop", 1),))
    tracer = Tracer()
    mem = MemorySink()
    obs = Observability(hub=MetricsHub([mem]), tracer=tracer, monitor=True)
    eng, state, batch_fn = _small_engine(obs=obs, faults=faults)
    for e in range(4):
        state, _ = eng.run_epoch(state, e, batch_fn)
    names = {s.name for s in tracer.spans}
    assert names == set(ENGINE_SPANS)
    epochs = [s for s in tracer.spans if s.name == "epoch"]
    assert len(epochs) == 4
    for ep in epochs:
        kids = [s for s in tracer.spans if s.parent is ep]
        # every phase once per epoch, in the order the host runs them
        assert [k.name for k in kids] == list(ENGINE_SPANS[1:])
        assert all(ep.encloses(k) for k in kids)
    causes = [ev["args"]["cause"] for ev in tracer.instants
              if ev["name"] == "compile"]
    # M=3 cold trace, then the fault surgery re-jits at M=2
    assert causes == ["first_trace", "federation_size_change"]
    assert eng.compile_counts() == {3: 1, 2: 1}
    validate_chrome_trace(tracer.to_chrome())
    # the hub-side history matches what the engine returned per epoch
    assert len(mem.history()["loss"]) == 4


def test_superepoch_emits_the_same_phases_once_per_block():
    tracer = Tracer()
    topo = FLTopology(num_servers=3, clients_per_server=2, t_client=2,
                      t_server=3, graph_kind="ring")
    task = make_regression_task(topo, RegressionSpec(), seed=0)
    eng = make_engine(topo, task["loss_fn"], sgd(1e-3), superepoch=2,
                      obs=Observability(tracer=tracer))
    state = init_dfl_state(eng.cfg, jnp.zeros((2,)), sgd(1e-3),
                           jax.random.key(0))
    eng.run(state, 4, task["batch_fn"])
    blocks = [s for s in tracer.spans if s.name == "superepoch"]
    assert len(blocks) == 2
    for blk in blocks:
        assert [k.name for k in tracer.spans if k.parent is blk] \
            == list(ENGINE_SPANS[1:])
    causes = [ev["args"]["cause"] for ev in tracer.instants
              if ev["name"] == "compile"]
    assert causes == ["first_trace"]


def _profiled_spans(obs, epochs=3):
    """Host annotations of ``epochs`` engine epochs under jax.profiler."""
    import glob
    import tempfile

    from jax.profiler import ProfileData

    eng, state, batch_fn = _small_engine(obs=obs)
    state, _ = eng.run_epoch(state, 0, batch_fn)      # compile outside
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    for e in range(1, 1 + epochs):
        state, _ = eng.run_epoch(state, e, batch_fn)
    jax.profiler.stop_trace()
    path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0]
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name in ENGINE_SPANS]


@pytest.mark.parametrize("obs", ["off", "tracer"])
def test_engine_spans_are_profiler_annotations(obs):
    """Every engine phase is a jax.profiler annotation, once per epoch
    and inside its epoch, whether a tracer is attached or not."""
    bundle = None if obs == "off" else Observability(tracer=Tracer())
    spans = _profiled_spans(bundle)
    epochs = [(s, e) for n, s, e in spans if n == "epoch"]
    assert len(epochs) == 3
    for s0, e0 in epochs:
        inner = sorted((s, n) for n, s, e in spans
                       if n != "epoch" and s0 <= s and e <= e0)
        assert [n for _, n in inner] == list(ENGINE_SPANS[1:])
