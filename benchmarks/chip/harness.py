"""One run of one benchmark cell: set-up, the measured window, the check.

A cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
(model sizes and the federation's layout), a traffic file (the epoch
schedule and the token distribution) and, under ``limits/``, the limits of
the numbers that decide ``correct``.  The configuration names its model
plug-in (``models/``), which knows the model's block: the trainer's
configuration for it, the seeded weights, the plain loss, the FLOPs and
the model's own named scopes.  Nothing here is specific to a cell or a
model.

Set-up assembles the federation with the calls ``launch/train.py``'s
``train_dynamic`` makes (topology, loss, SGD, consensus backend,
``make_engine``, ``init_dfl_state``, and on several chips the server mesh
placement), from weights and tokens that the benchmark makes from the
seed.  It then drives that engine through its first ``CHECK_STEPS``
epochs with ``run_epoch`` (the first compiles), and records each epoch's
loss and each leaf's distance from the initial weights.  The window goes
on with the same engine and state: ``run_epoch`` in a loop for
``--seconds``; each call reads the epoch's metrics back, so the host
clock after it marks the end of that epoch on the chip.  After the window
the peak of device memory is read, the trainer's state is freed, and the
plain reference (``reference.py``) replays the first epochs for the check.
A traced run (``--trace 1``) also compiles the epoch program afresh once
the peak is read, for the named scopes on its ops and its memory, and
hands both to the per-layer metrics' readers (``metrics/``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.compilation_cache import compilation_cache

from repro.configs import get_arch
from repro.core import FLTopology, init_dfl_state, make_engine
from repro.launch import sharding as shd
from repro.launch.train import resolve_consensus_backend, server_mesh
from repro.models import transformer as tf
from repro.optim import sgd

from benchmarks.chip import flops, models, phases, reference, trace_reduce
from benchmarks.chip.traffic import TokenStream

CHIP = pathlib.Path(__file__).resolve().parent
ROOT = CHIP.parents[1]
CHECK_STEPS = 3
CAPTURE = (0, CHECK_STEPS - 1)
TRACE_EPOCHS = 3
# a leaf whose reference change after the first epoch is under this share
# of the median leaf's moves by rounding alone, and is not compared
STILL_LEAF = 1e-3
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    # the benchmark's directory in the checkout the cell came from
    home: pathlib.Path = CHIP


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of the checkout at ``root``."""
    home = root / CHIP.relative_to(ROOT)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; there are {sorted(cells)}")
    wl = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    return Cell(
        name=name, chips=wl["chips"],
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads(
            (home / "traffic" / f"{wl['traffic']}.json").read_text()),
        limits=json.loads((home / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        home=home)


class Federation:
    """The trainer's federation for one configuration and traffic mix:
    the engine, and fresh seeded states for it.  ``models_dir``: the
    directory of the model plug-ins."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 models_dir: pathlib.Path = models.HERE):
        self.config, self.traffic = config, traffic
        self.model = models.load(config, models_dir)
        self.arch = self.model.arch_config(config,
                                           get_arch(config["program_arch"]))
        self.topo = FLTopology(
            num_servers=config["servers"],
            clients_per_server=config["clients_per_server"],
            t_client=traffic["t_client"], t_server=traffic["t_server"],
            graph_kind=config["graph"], mixing="metropolis")
        self.optimizer = sgd(config["gamma"])
        loss_fn = tf.make_loss_fn(
            self.arch, tf.ApplyOptions(**self.model.apply_options(config)))
        self._weights = jax.jit(
            lambda key: self.model.init_weights(key, config))
        abstract = jax.eval_shape(self._weights, jax.random.key(0))
        want = jax.eval_shape(lambda k: tf.init_params(k, self.arch),
                              jax.random.key(0))
        if (jax.tree.structure(abstract) != jax.tree.structure(want)
                or jax.tree.leaves(abstract) != jax.tree.leaves(want)):
            raise ValueError("the benchmark's weights do not have the "
                             "trainer's parameter layout")
        wire = dict(compression=config["compression"],
                    error_feedback=config["error_feedback"],
                    wire=config["wire"])
        mode, backend = resolve_consensus_backend(
            config["consensus_backend"], config["consensus_mode"], self.topo,
            abstract, **wire)
        self.engine = make_engine(self.topo, loss_fn, self.optimizer,
                                  consensus_mode=mode, mixing="symmetric",
                                  consensus_backend=backend, **wire)
        self._norms = jax.jit(lambda cp, w0: jax.tree.map(
            lambda x, w: jnp.sqrt(jnp.sum(jnp.square(x[:, 0] - w),
                                          axis=tuple(range(1, x.ndim - 1)))),
            cp, w0))
        self._shardings = None

    def weights(self, seed: int):
        return self._weights(jax.random.fold_in(reference.seed_key(seed), 0))

    def new_state(self, seed: int):
        """Weights from the seed, replicated to every client, placed one
        server per chip where the configuration says so."""
        params = self.weights(seed)
        state = init_dfl_state(self.engine.cfg, params, self.optimizer,
                               jax.random.fold_in(reference.seed_key(seed), 1))
        del params
        if self.config["placed"]:
            if self._shardings is None:
                mesh = server_mesh(self.topo.num_servers)
                state_sh = shd.named(
                    shd.fl_state_specs(state, mesh, tp_axis=None), mesh)
                batch_sh = jax.sharding.NamedSharding(
                    mesh, shd.fl_batch_spec(mesh, batch_div_replica=False))
                self._shardings = (state_sh, batch_sh)
                self.engine = dataclasses.replace(
                    self.engine, shardings=self._shardings)
            state = jax.device_put(state, self._shardings[0])
        return state

    def norms(self, state, seed: int) -> Dict[str, np.ndarray]:
        """Per leaf, each server's distance from the initial weights."""
        w0 = self.weights(seed)
        out = jax.device_get(self._norms(state.client_params, w0))
        del w0
        flat = jax.tree_util.tree_flatten_with_path(out)[0]
        return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
                for p, v in flat}

    def tokens_per_epoch(self) -> int:
        c = self.config
        return (c["servers"] * c["clients_per_server"]
                * c["per_client_batch"] * c["seq_len"]
                * self.traffic["t_client"])


def batches(cell: "Cell", seed: int) -> Callable:
    """The engine's ``batch_fn`` over the cell's token stream."""
    stream = token_stream(cell.config, cell.traffic, seed)

    def batch_fn(epoch, alive):
        return {"tokens": stream.epoch_tokens(epoch)[:, list(alive)]}

    return batch_fn


def token_stream(config, traffic, seed: int) -> TokenStream:
    shape = (traffic["t_client"], config["servers"],
             config["clients_per_server"], config["per_client_batch"],
             config["seq_len"])
    return TokenStream(traffic, config["vocab_size"], shape, seed)


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------


def readings(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared with their limits.

    ``loss_gap``: the largest relative gap of an epoch's loss over the
    first epochs.  ``update_gap`` (after the first epoch) and
    ``change_gap`` (after the last checked one): over every leaf and
    server, the gap between the trainer's and the reference's norm of the
    change from the initial weights, over the larger of the reference's
    norm of that leaf and of the median leaf.  Leaves that the reference
    moves by under ``STILL_LEAF`` of the median leaf after the first epoch
    are left out."""
    loss = max((abs(p - r) / abs(r) if math.isfinite(p) else math.inf)
               for p, r in zip(prog["loss"], ref["loss"]))

    def worst(step):
        return max([0.0] + [float(g) for g in
                            leaf_gaps(prog, ref, step).values()])

    return {"loss_gap": loss, "update_gap": worst(CAPTURE[0]),
            "change_gap": worst(CAPTURE[-1])}


def leaf_gaps(prog: Dict[str, Any], ref: Dict[str, Any], step: int
              ) -> Dict[str, float]:
    """Per compared leaf, the largest gap over its servers (see
    ``readings``)."""
    first = ref["norms"][CAPTURE[0]]
    med_first = float(np.median(np.concatenate(list(first.values()))))
    r_all, p_all = ref["norms"][step], prog["norms"][step]
    med = float(np.median(np.concatenate(list(r_all.values()))))
    out = {}
    for key, r in r_all.items():
        p = p_all[key]
        keep = first[key] >= STILL_LEAF * med_first
        gap = np.abs(p - r) / np.maximum(r, med)
        gap = np.where(np.isfinite(p), gap, np.inf)[keep]
        if gap.size:
            out[key] = float(gap.max())
    return out


def run_reference(cell: Cell, w0, seed: int, dtype=jnp.float32
                  ) -> Dict[str, Any]:
    """The reference's first epochs from ``w0`` on the cell's tokens."""
    stream = token_stream(cell.config, cell.traffic, seed)
    model = models.load(cell.config, cell.home / "models")
    ref = reference.Reference(cell.config, cell.traffic, model, dtype=dtype)
    return ref.run(w0, stream.epoch_tokens, seed, CHECK_STEPS, CAPTURE)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def compile_watch():
    """Counts JAX trace, compile and compile-cache events in the block;
    yields a one-element list that holds the count."""
    count = [0]

    def listen(event, *args, **kwargs):
        if event in COMPILE_EVENTS:
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield count
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _reader(name: str, metrics_dir: pathlib.Path) -> Callable:
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name}", metrics_dir / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def first_steps(fed: Federation, state, seed: int, batch_fn):
    """Drive the engine through its first epochs (the first one compiles)
    and record what the check compares."""
    prog = {"loss": [], "norms": {}}
    for step in range(CHECK_STEPS):
        state, rec = fed.engine.run_epoch(state, step, batch_fn)
        prog["loss"].append(rec["loss"])
        if step in CAPTURE:
            prog["norms"][step] = fed.norms(state, seed)
    return state, prog


def window(fed: Federation, state, epoch0: int, batch_fn, seconds: float):
    """``run_epoch`` until ``seconds`` have passed; returns the state, the
    records and the seconds from the start to the last epoch's return."""
    records = []
    t0 = time.perf_counter()
    t = t0
    while t - t0 < seconds:
        state, rec = fed.engine.run_epoch(state, epoch0 + len(records),
                                          batch_fn)
        records.append(rec)
        t = time.perf_counter()
    return state, records, t - t0


def traced_epochs(fed: Federation, state, epoch0: int, batch_fn,
                  keep: Optional[str] = None):
    """``TRACE_EPOCHS`` epochs under the profiler, each ``run_epoch`` in an
    ``engine`` span and each batch in an ``input`` span; returns the state,
    the records and the loaded trace (a copy of the raw trace goes to
    ``keep`` where given)."""
    def traced_batch(epoch, alive):
        with jax.profiler.TraceAnnotation("input"):
            return batch_fn(epoch, alive)

    tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    records = []
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans, no Python calls
        jax.profiler.start_trace(tdir, profiler_options=options)
        for k in range(TRACE_EPOCHS):
            with jax.profiler.TraceAnnotation("engine"):
                state, rec = fed.engine.run_epoch(state, epoch0 + k,
                                                  traced_batch)
            records.append(rec)
        jax.profiler.stop_trace()
        path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0]
        trace = trace_reduce.load(path)
        if keep:
            shutil.copy(path, keep)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return state, records, trace


def program_afresh(fed: Federation, state, epoch: int, batch_fn
                   ) -> Tuple[str, Dict[str, int]]:
    """The epoch program that ``run_epoch(state, epoch, batch_fn)`` would
    run, compiled anew: its HLO text and its memory analysis (argument,
    output and temporary bytes a chip).  JAX's in-memory caches are
    cleared and the persistent cache is off for the compile: that cache's
    key leaves the ops' metadata out, so a program from it carries the
    scope names of whichever compile filled it.  Nothing runs."""
    was = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        program = fed.engine.epoch_program(state, epoch, batch_fn)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    mem = program.memory_analysis()
    return program.as_text(), {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes)}


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, plant: Optional[Callable] = None,
        devices=None, keep_trace: Optional[str] = None) -> Dict[str, Any]:
    """One run of ``cell``: the result line's dict.  ``t_start`` is the
    process's start on the ``time.perf_counter`` clock; ``plant(fed)``
    breaks the trainer under the harness (the fault tests use it)."""
    devices = devices or jax.devices()[:cell.chips]
    fed = Federation(cell.config, cell.traffic, cell.home / "models")
    batch_fn = batches(cell, seed)
    state = fed.new_state(seed)
    if plant is not None:
        plant(fed)
    state, prog = first_steps(fed, state, seed, batch_fn)
    counts = fed.engine.compile_counts()
    red = tr = None
    with compile_watch() as compiles:
        setup_s = time.perf_counter() - t_start
        state, records, elapsed = window(fed, state, CHECK_STEPS, batch_fn,
                                         seconds)
        tokens_per_s = len(records) * fed.tokens_per_epoch() / elapsed
        if trace:
            state, extra, tr = traced_epochs(
                fed, state, CHECK_STEPS + len(records), batch_fn, keep_trace)
            records += extra
            red = trace_reduce.reduce(tr)
    window_compiles = compiles[0]
    retraced = int(fed.engine.compile_counts() != counts)
    # the CPU backend of the tests reports no memory statistics
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    failed = sum(not math.isfinite(r["loss"]) for r in records)
    if trace:
        hlo, memory = program_afresh(fed, state, CHECK_STEPS + len(records),
                                     batch_fn)
        print("epoch program: " + ", ".join(
            f"{k} {v}" for k, v in memory.items()), file=sys.stderr)
    del state
    fed.engine = None
    gc.collect()

    t_ref = time.perf_counter()
    ref = run_reference(cell, fed.weights(seed), seed)
    print(f"reference: {time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    values = readings(prog, ref)
    values["window_compiles"] = window_compiles + retraced
    limits = dict(cell.limits, window_compiles=0)
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        ctx = {"trace": red, "tokens_per_s": tokens_per_s,
               "flops_per_token": fed.model.train_flops_per_token(
                   cell.config, cell.config["seq_len"]),
               "peak": (flops.peaks(dev.device_kind)
                        if dev.platform == "tpu" else None),
               "chips": len(devices), "records": records, "memory": memory,
               **phases.trace_context(tr, red, hlo, fed.model.SCOPES)}
        metrics = {}
        for m in cell.per_layer:
            v = _reader(m["name"], cell.home / "metrics")(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red:
            device["busy_s"] = (sum(r.busy_ns for r in red.values())
                                / len(red) * 1e-9)
            device["window_s"] = next(iter(red.values())).window_ns * 1e-9
    else:
        e2e = {"tokens_per_s": tokens_per_s, "peak_hbm_gb": peak / 1e9,
               "setup_s": setup_s}
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": e2e[k], "unit": units[k]} for k in units}
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if trace and red:
        result["breakdown"] = trace_reduce.breakdown(tr, red)
    result["checks"] = checks
    return result


def report(result: Dict[str, Any]) -> None:
    """Print the compared numbers on standard error, then the result line
    as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
