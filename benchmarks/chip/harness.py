"""The benchmark harness: finds a cell's files by name, builds its inputs
from the seed, warms every shape the cell's traffic uses, runs passes over
the traffic's pool of sessions through the public entry for ``--seconds``,
reduces what it saw to metrics, and compares what the timed path produced
with the plain reference.

A traffic file fixes the work: its ``protocol_seed`` deals the population
and drives each pool session's protocol (sampling, churn, network), so
every seed's pass runs the same rounds and trainings. The run's seed makes
the data and the weights and draws the order of the pool.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name in
``BENCHMARK.json``:

* ``configs/<config>.json`` — model, training, dataset and limits;
* ``traffic/<traffic>.json`` — session kind, population, profile, lengths;
* ``datasets/<kind>.py``, ``profiles/<kind>.py``, ``sessions/<kind>.py``
  — the generators and session builders the data files name;
* ``reference/<family>.py`` — the plain reference of a model family;
* ``counts/<family>.py`` — the operation count of a model family;
* ``layers/<metric>.py`` — one reader per per-layer metric.

A config names its task factory and the factory's keyword arguments
(``task``), the engine counters its readers read (``counters``), and
where the check's reference runs (``reference_device``).

A cell's ``chips`` decide its engine (:func:`engine_factory`): one chip
runs the batched engine the sessions pick themselves, four run every
session, and the warm-up, on a ``MeshEngine`` over the cell's devices.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.abspath(os.path.join(HERE, "..", ".."))
for _p in (os.path.join(REPO, "src"), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# sampled answers of each kind checked per run, unless the config's
# ``check`` key sets them: training jobs, aggregations, evaluated models
SAMPLES = {"train": 8, "agg": 8, "eval": 4}
# where the check's reference may run (a config's ``reference_device``)
REFERENCE_DEVICES = ("cpu", "chip")


# ----------------------------------------------------------------- discovery


def manifest(root: str = REPO) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(m: dict, name: str) -> dict:
    for w in m["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(known: {[w['name'] for w in m['workloads']]})")


def load_json(kind: str, name: str, base: str = HERE) -> dict:
    path = os.path.join(base, kind, name + ".json")
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} file for {name!r} at {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, base: str = HERE):
    """``<base>/<kind>/<name>.py`` as a module of its own; its directory
    is importable, so a file may share helpers with its siblings."""
    path = os.path.join(base, kind, name + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} module for {name!r} at {path}")
    folder = os.path.dirname(path)
    if folder not in sys.path:
        sys.path.insert(0, folder)
    mod_name = f"chipbench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def readers(m: dict, workload_name: str, base: str = HERE
            ) -> Dict[str, Callable]:
    """The per-layer metrics this cell reports, name -> ``read(run)``."""
    out = {}
    for metric in m["per_layer"]:
        if workload_name in metric.get("workloads", [workload_name]):
            out[metric["name"]] = load_module("layers", metric["name"],
                                              base).read
    return out


def session_seed(seed: int, index: int) -> int:
    """A seed the program's generators take (< 2**31) for session
    ``index`` of the run with ``seed``."""
    state = np.random.SeedSequence([seed, index % 2**32]).generate_state(
        1)[0]
    return int(state) % (2**31 - 1)


def pass_order(seed: int, sessions: int) -> List[int]:
    """The order, drawn from ``seed``, in which a window runs the
    traffic's pool of ``sessions`` sessions."""
    rng = np.random.default_rng([seed, 17])
    return [int(k) for k in rng.permutation(sessions)]


# --------------------------------------------------------------- the inputs


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    seed: int
    task: object = None
    data: object = None
    profile: object = None
    builder: Callable = None
    reference: object = None
    shards: List[tuple] = field(default_factory=list)
    test: tuple = ()
    new_engine: Callable = None           # a fresh engine for a session
    devices: Optional[list] = None        # the cell's chips (None: one)


def train_config(config: dict, seed: int = 0):
    from repro.config import TrainConfig

    return TrainConfig(**config["train"], seed=seed)


def build_task(config: dict):
    """The config's task: ``task.factory`` of ``repro.models.tasks`` with
    the train config as ``tcfg=`` and ``task.overrides`` as further
    keywords (for ``lm_task``: ``arch``, ``reduce`` and the model's
    overrides)."""
    import repro.models.tasks as tasks

    factory = getattr(tasks, config["task"]["factory"])
    return factory(tcfg=train_config(config), **config["task"]["overrides"])


def build_profile(traffic: dict, seed: int, base: str = HERE):
    from repro.traces.availability import AvailabilityTimeline
    from repro.traces.profile import TraceProfile

    p = traffic["profile"]
    g = load_module("profiles", p["kind"], base).make(p, traffic["nodes"],
                                                      seed)
    return TraceProfile(
        name=p["kind"], seed=0, speeds=g["speeds"], uplink=g["uplink"],
        downlink=g["downlink"], latency=g["latency"], city=g["city"],
        availability=tuple(AvailabilityTimeline(intervals=w,
                                                period=g["period"])
                           for w in g["windows"]))


def engine_factory(task, devices: Optional[list] = None) -> Callable:
    """``new_engine()`` for a cell on ``devices``. On one device (or
    ``None``), the engine a session picks for the task itself. On more, a
    ``MeshEngine`` whose mesh is exactly those devices: the program's
    ``"sharded"`` engine where they are every local device, else one the
    harness builds over them."""
    from repro.engine.cohort import MeshEngine, make_engine

    if devices is None or len(devices) == 1:
        return lambda: make_engine(None, task)
    import jax

    if list(devices) == list(jax.devices()):
        return lambda: make_engine("sharded", task)
    from jax.sharding import AxisType, Mesh

    mesh = Mesh(np.array(devices).reshape(1, len(devices)),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    return lambda: MeshEngine(task, mesh)


@contextlib.contextmanager
def given_engine(engine):
    """Inside this block a session of ``repro.sim.runner`` takes
    ``engine``: the sessions pick their engine by kind as they are built
    (``make_engine``) and take no engine object."""
    import repro.sim.runner as runner

    real = runner.make_engine
    runner.make_engine = lambda kind, task: engine
    try:
        yield engine
    finally:
        runner.make_engine = real


def build_cell(name: str, config: dict, traffic: dict, seed: int,
               base: str = HERE, task=None, devices=None) -> Cell:
    """The cell's inputs from ``seed``; ``task`` reuses a task built from
    the same config (and its compiled programs); ``devices`` are the
    cell's chips (:func:`engine_factory`)."""
    from repro.data.loader import ClientDataset, FederatedData

    where = config.get("reference_device", "cpu")
    if where not in REFERENCE_DEVICES:
        raise ValueError(f"reference_device {where!r} is not one of "
                         f"{REFERENCE_DEVICES}")
    if where == "chip":
        import jax

        first = (devices or jax.devices())[0]
        if first.platform == "cpu":
            raise ValueError("reference_device 'chip' needs an accelerator; "
                             "the cell's first device is a CPU")
    cell = Cell(name, config, traffic, seed, devices=devices)
    ds = config["dataset"]
    gen = load_module("datasets", ds["kind"], base).make(
        ds, traffic["nodes"], seed)
    cell.shards, cell.test = gen["clients"], gen["test"]
    cell.data = FederatedData(
        clients=[ClientDataset(x, y) for x, y in cell.shards],
        test=ClientDataset(*cell.test), task=ds["kind"])
    cell.profile = build_profile(traffic, traffic["protocol_seed"], base)
    cell.task = task if task is not None else build_task(config)
    cell.new_engine = engine_factory(cell.task, devices)
    cell.builder = load_module("sessions", traffic["session"], base).build
    cell.reference = load_module("reference", config["model"]["family"],
                                 base)
    return cell


def new_session(cell: Cell, index: int):
    """Session ``index`` of the traffic's pool (``-1`` is the warm-up
    session). Its protocol (sampling, churn, network) comes from the
    traffic's ``protocol_seed``, so every run's pool does the same work;
    its weights and batch order come from the run's seed. It runs on a
    fresh engine of the cell's (:func:`engine_factory`)."""
    return cell.builder(
        task=cell.task, data=cell.data, profile=cell.profile,
        traffic=cell.traffic,
        tcfg=train_config(cell.config, session_seed(cell.seed, index)),
        seed=session_seed(cell.traffic["protocol_seed"], index),
        engine=cell.new_engine())


# ------------------------------------------------------------------- warm-up


def warm_shapes(cell: Cell, log=None) -> int:
    """Compile every program shape the cell's traffic can reach, through
    the engine's public calls: each cohort group (``S`` jobs of the same
    step count ``T``, for every ``T`` in the shards and ``S`` up to the
    traffic's ``warm.max_group``), each aggregation of ``1..max_agg``
    models and each evaluation sweep of ``1..max_eval`` models, on an
    engine of the cell's, so its sessions find every program compiled.
    Returns the number of engine calls made."""
    warm = cell.traffic["warm"]
    task, tcfg = cell.task, train_config(cell.config)
    engine = cell.new_engine()
    params = task.init_params(0)
    bs, epochs = tcfg.batch_size, cell.traffic["local_epochs"]
    by_steps: Dict[int, list] = {}
    for i, c in enumerate(cell.data.clients):
        by_steps.setdefault(math.ceil(len(c) / bs) * epochs, []).append(i)
    calls, tag = 0, 0
    out = None
    t0 = time.perf_counter()
    for steps in sorted(by_steps):
        nodes = by_steps[steps]
        for s in range(1, min(len(nodes), warm["max_group"]) + 1):
            tag += 1
            for i in nodes[:s]:
                engine.submit(f"w{i}", tag, params, cell.data.clients[i],
                              batch_size=bs, epochs=epochs, seed=tag)
            i = nodes[0]
            out = engine.result(f"w{i}", tag, params, cell.data.clients[i],
                                batch_size=bs, epochs=epochs, seed=tag)
            calls += 1
        if log is not None:
            log(f"warm: {steps} steps x up to {s} jobs, {calls} calls, "
                f"{time.perf_counter() - t0:.1f} s")
    models = [out] * warm["max_agg"]
    for p in range(1, warm["max_agg"] + 1):
        out = engine.aggregate(models[:p])
        calls += 1
    for m in range(1, warm["max_eval"] + 1):
        engine.evaluate_models([out] * m, cell.data.test)
        calls += 1
    # a result asked for with an equal model under another object (two
    # aggregators of one round): the engine matches the two by value
    twin, tag, i = engine.aggregate([out]), tag + 1, by_steps[steps][0]
    engine.submit(f"w{i}", tag, out, cell.data.clients[i], batch_size=bs,
                  epochs=epochs, seed=tag)
    engine.result(f"w{i}", tag, twin, cell.data.clients[i], batch_size=bs,
                  epochs=epochs, seed=tag)
    calls += 2
    import jax

    jax.block_until_ready(out.buffer if hasattr(out, "buffer") else out)
    return calls


# -------------------------------------------------------- the timed sessions


class HostModel:
    """A model the program produced, copied to host memory: its flat
    float32 buffer as a numpy array and the spec that names its leaves."""

    def __init__(self, buffer: np.ndarray, spec):
        self.buffer, self.spec = buffer, spec

    def tree(self) -> dict:
        out = []
        for off, size, shape, dt in zip(self.spec.offsets, self.spec.sizes,
                                        self.spec.shapes, self.spec.dtypes):
            x = self.buffer[off:off + size].reshape(shape)
            if np.issubdtype(dt, np.integer):
                x = np.rint(x)
            out.append(x.astype(dt))
        return self.spec.treedef.unflatten(out)


def _device_arrays(x) -> list:
    """The device arrays of a sampled answer: of its models (a
    ``FlatModel`` is a leaf of the tree) and of its other leaves."""
    import jax

    return [a for a in (getattr(v, "buffer", v) for v in jax.tree.leaves(x))
            if isinstance(a, jax.Array)]


def _to_host(x):
    """``x`` with every model a :class:`HostModel` and every other device
    array a numpy array."""
    import jax

    from repro.engine.flat import FlatModel

    def one(v):
        if isinstance(v, FlatModel):
            return HostModel(np.asarray(v.buffer), v.spec)
        return np.asarray(v) if isinstance(v, jax.Array) else v

    return jax.tree.map(one, x)


class Sample:
    """A sampled answer on its way to host memory. When kept, the copy of
    its device arrays back to the host starts (it runs once the device has
    produced them); :meth:`settle` swaps them for host arrays once they
    are there, and from then on the sample holds no device array."""

    def __init__(self, item: tuple):
        self.item = item
        self.arrays = _device_arrays(item)
        for a in self.arrays:
            a.copy_to_host_async()

    @property
    def ready(self) -> bool:
        return all(a.is_ready() for a in self.arrays)

    def settle(self) -> None:
        if self.arrays:
            self.item, self.arrays = _to_host(self.item), []


class Reservoir:
    """A uniform sample of ``k`` of the offers, drawn from ``rng``; an
    offer that is kept becomes a :class:`Sample` (``keep``)."""

    def __init__(self, k: int, rng, keep: Callable):
        self.k, self.rng, self.seen, self.kept = k, rng, 0, []
        self.keep = keep

    def offer(self, item):
        """What the reservoir keeps of ``item``, or None."""
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept.append(self.keep(item))
            return self.kept[-1]
        j = int(self.rng.integers(0, self.seen))
        if j < self.k:
            self.kept[j] = self.keep(item)
            return self.kept[j]
        return None

    @property
    def items(self) -> list:
        return [getattr(s, "item", s) for s in self.kept]


def _ready(models) -> None:
    """Wait until every model's parameters are on the device."""
    import jax

    jax.block_until_ready([getattr(m, "buffer", m) for m in models])


class _Stamped(list):
    """``SessionResult.round_times`` that notes the wall clock of each
    round's completion as the session appends it."""

    def __init__(self, items, walls):
        super().__init__(items)
        self._walls = walls

    def append(self, item):
        self._walls.append(time.perf_counter())
        super().append(item)


def sample_counts(config: dict) -> Dict[str, int]:
    """Sampled answers of each kind a run checks: the config's ``check``
    key over :data:`SAMPLES`."""
    return {**SAMPLES, **config.get("check", {})}


class Recorder:
    """Wraps the public calls of each session's engine: host spans for the
    trace, sampled answers for the check (copied to host memory: see
    :class:`Sample`), and counts. With :attr:`note_programs` it also notes
    the argument shapes of the cohort step programs each call runs, to
    compile their text for the trace's op scopes."""

    def __init__(self, seed: int, samples: Dict[str, int] = SAMPLES):
        rng = np.random.default_rng([seed, 99])
        self.pending: List[Sample] = []
        self.train = Reservoir(samples["train"], rng, self._keep)
        self.agg = Reservoir(samples["agg"], rng, self._keep)
        self.evals = Reservoir(samples["eval"], rng, self._keep)
        self.longest = None               # Sample: the job with most
        self.longest_n = 0                # samples, and their number
        self.samples_trained = 0
        self.agg_sizes: List[int] = []
        # host seconds inside each public call, for the run's log
        self.host_s = {"result": 0.0, "aggregate": 0.0, "evaluate": 0.0}
        self.copy_s = 0.0                 # host seconds copying samples
        # in the traced session, the aggregation and evaluation spans
        # start once their inputs are on the device and end once their
        # answer is, so that they hold that layer's work and no other
        self.sync = False
        self.note_programs = False
        self.calls: Dict[tuple, tuple] = {}   # (program, shapes) -> args

    def _keep(self, item) -> Sample:
        t0 = time.perf_counter()
        s = Sample(item)
        if s.arrays:
            self.pending.append(s)
        self.copy_s += time.perf_counter() - t0
        return s

    def settle(self, wait: bool = False) -> None:
        """Swap the kept samples whose copies are done (``wait``: all)
        for host arrays."""
        if not self.pending:
            return
        t0 = time.perf_counter()
        left = []
        for s in self.pending:
            if wait or s.ready:
                s.settle()
            else:
                left.append(s)
        self.pending = left
        self.copy_s += time.perf_counter() - t0

    def attach(self, engine) -> None:
        from jax.profiler import TraceAnnotation

        result, aggregate = engine.result, engine.aggregate
        evaluate = engine.evaluate_models

        def traced_result(node_id, tag, params, client, *, batch_size,
                          epochs, seed, lr_scale=1.0):
            self.settle()
            t0 = time.perf_counter()
            with TraceAnnotation("bench.result"):
                out = result(node_id, tag, params, client,
                             batch_size=batch_size, epochs=epochs,
                             seed=seed, lr_scale=lr_scale)
            self.host_s["result"] += time.perf_counter() - t0
            n = len(client) * epochs
            self.samples_trained += n
            job = (params, client, batch_size, epochs, seed, lr_scale, out)
            kept = self.train.offer(job)
            if self.longest is None or n > self.longest_n:
                self.longest, self.longest_n = kept or self._keep(job), n
            return out

        def traced_aggregate(models, weights=None):
            self.settle()
            if self.sync:
                _ready(models)
            t0 = time.perf_counter()
            with TraceAnnotation("bench.aggregate"):
                out = aggregate(models, weights)
                if self.sync:
                    _ready([out])
            self.host_s["aggregate"] += time.perf_counter() - t0
            self.agg_sizes.append(len(models))
            self.agg.offer((list(models), weights, out))
            return out

        def traced_evaluate(models, test):
            self.settle()
            if self.sync:
                _ready(models)
            t0 = time.perf_counter()
            with TraceAnnotation("bench.evaluate"):
                out = evaluate(models, test)
            self.host_s["evaluate"] += time.perf_counter() - t0
            for m, metrics in zip(models, out):
                self.evals.offer((m, metrics))
            return out

        engine.result = traced_result
        engine.aggregate = traced_aggregate
        engine.evaluate_models = traced_evaluate
        if self.note_programs:
            self._watch_programs(engine)

    def _watch_programs(self, engine) -> None:
        """Note the argument shapes (and, across chips, shardings) of each
        cohort program shape the engine runs."""
        import jax

        def abstract(a):
            sharding = getattr(a, "sharding", None)
            if sharding is None or len(sharding.device_set) < 2:
                return jax.ShapeDtypeStruct(a.shape, a.dtype)
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

        for attr in ("_step", "_scan"):
            fn = getattr(engine, attr, None)
            if fn is None:
                continue

            def watch(*args, _fn=fn):
                key = (_fn, args[0].shape, args[2].shape)
                if key not in self.calls:
                    self.calls[key] = jax.tree.map(abstract, args)
                return _fn(*args)

            setattr(engine, attr, watch)

    def program_texts(self, log=None) -> List[str]:
        """The compiled text of every cohort program shape noted."""
        out = []
        for (fn, _, _), args in self.calls.items():
            try:
                out.append(fn.lower(*args).compile().as_text())
            except Exception as e:            # the text is optional
                if log is not None:
                    log(f"no compiled text: {e!r}")
        return out


@dataclass
class SessionStats:
    rounds: int = 0                       # round completions
    events: int = 0
    flushes: int = 0
    jobs: int = 0
    # the engine's counters (None where the engine has no such counter)
    jobs_served: Optional[int] = None
    batch_bytes_h2d: Optional[int] = None
    shard_uploads: Optional[int] = None
    wall_s: float = 0.0
    round_walls: List[float] = field(default_factory=list)
    agg_sizes: List[int] = field(default_factory=list)
    evals: int = 0                        # models evaluated
    host_s: Dict[str, float] = field(default_factory=dict)
    # the engine counters the config lists, by name (None where the
    # engine has no such counter)
    counters: Dict[str, Optional[float]] = field(default_factory=dict)
    ok: bool = True


def finite_history(result) -> bool:
    for h in result.history:
        for k, v in h.items():
            if isinstance(v, float) and not math.isfinite(v):
                return False
    return True


def run_session(cell: Cell, index: int, recorder: Recorder,
                duration: Optional[float] = None) -> SessionStats:
    """Build and run session ``index`` of the cell's pool (for the
    traffic's ``session_seconds`` unless ``duration`` is given); raises
    what the session raises."""
    from jax.profiler import TraceAnnotation

    t0 = time.perf_counter()
    with TraceAnnotation("bench.build"):
        session = new_session(cell, index)
    stats = SessionStats()
    session.result.round_times = _Stamped(session.result.round_times,
                                          stats.round_walls)
    recorder.attach(session.engine)
    n_agg = len(recorder.agg_sizes)
    n_evals, host0 = recorder.evals.seen, dict(recorder.host_s)
    result = session.run(duration or cell.traffic["session_seconds"])
    stats.wall_s = time.perf_counter() - t0
    stats.evals = recorder.evals.seen - n_evals
    stats.host_s = {k: v - host0[k] for k, v in recorder.host_s.items()}
    stats.rounds = len(result.round_times)
    stats.events = session.sim.events_processed
    e = session.engine
    stats.flushes = getattr(e, "flushes", 0)
    stats.jobs = getattr(e, "jobs_run", 0)
    stats.jobs_served = getattr(e, "jobs_served", None)
    stats.batch_bytes_h2d = getattr(e, "batch_bytes_h2d", None)
    stats.shard_uploads = getattr(e, "shard_uploads", None)
    stats.counters = {name: getattr(e, name, None)
                      for name in cell.config.get("counters", ())}
    stats.agg_sizes = recorder.agg_sizes[n_agg:]
    stats.ok = finite_history(result)
    if len(stats.round_walls) != len(result.round_times):
        raise RuntimeError(f"{len(stats.round_walls)} wall stamps for "
                           f"{len(result.round_times)} rounds")
    return stats


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, from JAX's
    monitoring events (a cache hit reports its load as a compile)."""

    def __init__(self):
        import jax

        self.names: List[str] = []
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def count(self) -> int:
        return len(self.names)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(str(kw.get("fun_name", "?")))
            self.seconds += duration


@dataclass
class Window:
    seconds: float = 0.0
    attempted: int = 0
    failed: int = 0
    sessions: List[SessionStats] = field(default_factory=list)
    compiles: int = 0
    compiled: List[str] = field(default_factory=list)
    trace: object = None                  # trace_reduce.Reduction
    program: object = None                # program_trace.ProgramTrace
    traced: Optional[SessionStats] = None
    samples_trained: int = 0
    start: float = 0.0
    texts_s: float = 0.0                  # compiling the step programs'
                                          # text for the op scopes

    @property
    def rounds(self) -> int:
        """Round completions recorded in ``SessionResult.round_times``."""
        return sum(len(s.round_walls) for s in self.sessions)

    def round_gaps(self) -> List[float]:
        """Wall seconds between consecutive round completions, from the
        window's start, across session boundaries."""
        stamps = [self.start] + [t for s in self.sessions
                                 for t in s.round_walls]
        return [b - a for a, b in zip(stamps, stamps[1:])]


def run_window(cell: Cell, seconds: float, recorder: Recorder,
               compiles: CompileCounter, trace_dir: Optional[str] = None,
               log=print) -> Window:
    """Passes over the traffic's pool of sessions, back to back, each in
    the order :func:`pass_order` draws from the seed, until ``seconds``
    have passed; the pass running then finishes, so every window does
    whole passes: the same work for every seed. With ``trace_dir`` the
    second session runs under the profiler, inside a ``bench.window``
    span, with the recorder's spans synchronised
    (:attr:`Recorder.sync`); once the window has closed the trace is
    reduced twice: the benchmark's spans (:mod:`trace_reduce`) and the
    program's own (:mod:`program_trace`, op scopes from the compiled text
    of the step programs that session ran). Sampled answers still on their
    way to the host are waited for once the clock has stopped."""
    import jax
    from jax.profiler import ProfileOptions, TraceAnnotation

    options = ProfileOptions()
    options.host_tracer_level = 1         # the benchmark's spans, no more
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    win = Window()
    c0 = compiles.count
    order = pass_order(cell.seed, cell.traffic["pass_sessions"])
    win.start = t0 = time.perf_counter()
    i = 0
    while i % len(order) or (trace_dir is not None and i < 2) or \
            time.perf_counter() - t0 < seconds:
        tracing = trace_dir is not None and i == 1
        if tracing:
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        win.attempted += 1
        recorder.sync = recorder.note_programs = tracing
        try:
            with TraceAnnotation("bench.window" if tracing else
                                 "bench.session"):
                stats = run_session(cell, order[i % len(order)],
                                    recorder)
        except Exception:
            import traceback

            log(traceback.format_exc())
            win.failed += 1
            stats = None
        finally:
            recorder.sync = recorder.note_programs = False
            if tracing:
                jax.profiler.stop_trace()
        if stats is not None:
            win.sessions.append(stats)
            if not stats.ok:
                win.failed += 1
            if tracing:
                win.traced = stats
        i += 1
    win.seconds = time.perf_counter() - t0
    recorder.settle(wait=True)            # outside the timed window
    win.compiles = compiles.count - c0
    win.compiled = compiles.names[c0:]
    win.samples_trained = recorder.samples_trained
    if trace_dir is not None:
        path = find_xplane(trace_dir)
        if path:
            reduce_trace(win, path, recorder, log)
    return win


def reduce_trace(win: Window, path: str, recorder: Recorder,
                 log=print) -> None:
    """Reduce the traced session's ``.xplane.pb`` onto ``win``."""
    from jax.profiler import ProfileData

    import program_trace
    import trace_reduce

    data = ProfileData.from_file(path)
    # ``data.planes`` can be iterated once: each reduction asks anew
    win.trace = trace_reduce.reduce_planes(data.planes)
    t0 = time.perf_counter()
    scopes = program_trace.hlo_op_scopes(recorder.program_texts(log))
    win.texts_s = time.perf_counter() - t0
    win.program = program_trace.reduce_program(data.planes, scopes)


def find_xplane(root: str) -> Optional[str]:
    for dirpath, _, files in os.walk(root):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    return None


# -------------------------------------------------------------------- checks


def leaves(params) -> Dict[str, np.ndarray]:
    """A model the program produced, as float32 host arrays named by
    their path in the parameter tree (``"conv1"``, ``"layers/attn/wq"``)."""
    import jax

    from repro.engine.flat import as_tree

    tree = params.tree() if isinstance(params, HostModel) else \
        jax.device_get(as_tree(params))
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): np.asarray(v, np.float32)
            for path, v in flat}


def leaf_gaps(got: dict, want: dict, scale: dict) -> Dict[str, float]:
    """Per leaf, ``||got - want|| / max(scale_leaf, median scale)``.
    Leaves whose scale is under a thousandth of the median (moved by
    round-off alone) are left out."""
    norms = {k: float(np.linalg.norm(v)) for k, v in scale.items()}
    med = float(np.median(list(norms.values())))
    return {k: float(np.linalg.norm(got[k].astype(np.float64)
                                    - want[k].astype(np.float64)))
            / max(s, med)
            for k, s in norms.items() if s >= 1e-3 * med}


def leaf_gap(got: dict, want: dict, scale: dict) -> float:
    """The worst leaf of :func:`leaf_gaps`."""
    return max(leaf_gaps(got, want, scale).values())


def change_gaps(got: dict, want: dict, start: dict) -> Dict[str, float]:
    """Per leaf, the gap between the norms of the two changes from
    ``start``, ``| ||got - start|| - ||want - start|| |``, over the larger
    of that leaf's and the median leaf's norm of ``want``'s change. Leaves
    whose change is under a thousandth of the median are left out. (The
    norm of a change is steady where its direction is not: 25 steps from
    the same start drift apart under float32 round-off alone.)"""
    def norms(p):
        return {k: float(np.linalg.norm(p[k].astype(np.float64)
                                        - start[k].astype(np.float64)))
                for k in want}

    g, w = norms(got), norms(want)
    med = float(np.median(list(w.values())))
    return {k: abs(g[k] - w[k]) / max(w[k], med)
            for k in w if w[k] >= 1e-3 * med}


def model_cfg(cell: Cell, exact: bool = False) -> dict:
    """The model as the reference computes it. ``matmul_operands`` states
    how the chip's matrix unit takes float32 operands at the default
    precision; other backends (the CPU tests) compute them exactly, and so
    does ``exact``."""
    import jax

    model = dict(cell.config["model"])
    if exact or jax.default_backend() != "tpu":
        model["matmul_operands"] = "float32"
    return model


def train_gaps(cell: Cell, jobs, against=None, exact: bool = False
               ) -> List[float]:
    """Per sampled training job: the median leaf of :func:`change_gaps`
    between the program's parameters after the job and the reference's.
    (The worst leaf swings from job to job under round-off drift alone;
    the median is steady.) ``against`` replaces the program's answers
    (a stand-in: the control, or a planted fault); ``exact`` compares with
    the reference in exact float32."""
    gaps = []
    for params, client, bs, epochs, seed, lr_scale, out in jobs:
        start = leaves(params)
        train = dict(cell.config["train"])
        train["lr"] = train["lr"] * lr_scale
        kw = dict(batch_size=bs, epochs=epochs, seed=seed, train_cfg=train,
                  model_cfg=model_cfg(cell, exact))
        want = reference_on(cell, cell.reference.train, start, client.x,
                            client.y, **kw)
        got = (leaves(out) if against is None else
               against(start, client.x, client.y, **kw))
        gaps.append(float(np.median(list(
            change_gaps(got, want, start).values()))))
    return gaps


def agg_gaps(calls, against=None) -> List[float]:
    """Per sampled aggregation: the worst leaf of the distance between the
    program's weighted mean and the float64 mean of the same models, over
    the norm of that leaf of the mean."""
    gaps = []
    for models, weights, out in calls:
        rows = [leaves(m) for m in models]
        w = np.ones(len(rows)) if weights is None else np.asarray(
            weights, np.float64)
        want = {k: sum(wi * r[k].astype(np.float64)
                       for wi, r in zip(w, rows)) / w.sum()
                for k in rows[0]}
        got = leaves(out) if against is None else against(rows, w)
        gaps.append(leaf_gap(got, want, want))
    return gaps


def eval_gaps(cell: Cell, evals, against=None) -> Dict[str, List[float]]:
    """Per sampled evaluated model: the relative gap of each loss-like
    metric and the absolute gap of accuracy, against the reference's
    evaluation over the whole test set."""
    out: Dict[str, List[float]] = {"eval_loss_gap": [], "eval_acc_gap": []}
    x, y = cell.test
    for model, metrics in evals:
        p = leaves(model)
        want = reference_on(cell, cell.reference.evaluate, p, x, y,
                            model_cfg=model_cfg(cell))
        got = metrics if against is None else against(
            p, x, y, model_cfg=model_cfg(cell))
        out["eval_loss_gap"].append(
            abs(got.get("loss", math.inf) - want["loss"]) / abs(want["loss"]))
        if "accuracy" in want:
            out["eval_acc_gap"].append(
                abs(got.get("accuracy", math.inf) - want["accuracy"]))
    return out


def reference_on(cell: Cell, fn, *args, **kw):
    """Run the float32 reference where the config's ``reference_device``
    says: ``"cpu"`` (the default), the host's CPU, exact float32 and off
    the chip's memory; ``"chip"``, the cell's first device (an
    accelerator: :func:`build_cell` refuses a CPU), under the ``highest``
    matmul precision, so that float32 stays all but exact there too (the
    operand rounding that ``matmul_operands`` states is the reference's
    own and is kept). Either runs after the window's clock has stopped.

    The two places do not read alike: on a TPU the chip's reference reads
    the program's gaps lower than the host's. A config's limits, and the
    control's and the faults' readings they are set between, hold only for
    the place they were read at: a cell reads them with ``control.py`` at
    the ``reference_device`` it sets."""
    import jax

    if cell.config.get("reference_device", "cpu") == "chip":
        with jax.default_device((cell.devices or jax.devices())[0]), \
                jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    with jax.default_device(jax.devices("cpu")[0]):
        return fn(*args, **kw)


def sampled_jobs(recorder: Recorder) -> list:
    """The sampled training jobs, with the longest job of the window."""
    jobs = list(recorder.train.items)
    if recorder.longest is not None and all(
            s is not recorder.longest for s in recorder.train.kept):
        jobs.append(recorder.longest.item)
    return jobs


def stand_in(cell: Cell, kind: str) -> Dict[str, Callable]:
    """What takes the program's place for ``kind``: ``"control"``, the
    reference computed in the config's control dtype (the step below the
    precision the configuration states); ``"half_batch"``, the reference
    trained with the second half of every batch left out and the mean
    taken over the rest (a planted fault of the train step)."""
    ref = cell.reference
    if kind == "half_batch":
        def half(*a, **kw):
            return reference_on(cell, ref.train, *a,
                                rows=kw["batch_size"] // 2, **kw)

        return {"train": half}
    if kind != "control":
        raise KeyError(f"no stand-in {kind!r}")
    dtype = cell.config["control"]["dtype"]

    def train(*a, **kw):
        return ref.train(*a, dtype=dtype, **kw)

    def mean(rows, w):
        import jax.numpy as jnp

        ww = jnp.asarray(w / w.sum(), dtype)
        return {k: np.asarray(sum(wi * jnp.asarray(r[k], dtype)
                                  for wi, r in zip(ww, rows)), np.float32)
                for k in rows[0]}

    def evaluate(*a, **kw):
        return ref.evaluate(*a, dtype=dtype, **kw)

    return {"train": train, "agg": mean, "eval": evaluate}


def gap_lists(cell: Cell, recorder: Recorder, against=None
              ) -> Dict[str, List[float]]:
    """Each number compared, per sampled answer: of the program's answers
    or, where ``against`` (a :func:`stand_in`) names a part, of the
    stand-in's."""
    against = against or {}
    out = {"train_gap": train_gaps(cell, sampled_jobs(recorder),
                                   against=against.get("train"))}
    if "train" not in against or "agg" in against:
        out["agg_gap"] = agg_gaps(recorder.agg.items,
                                  against=against.get("agg"))
    if "train" not in against or "eval" in against:
        out.update(eval_gaps(cell, recorder.evals.items,
                             against=against.get("eval")))
    return out


def readings(cell: Cell, recorder: Recorder, against=None
             ) -> Dict[str, float]:
    """Each number compared: the worst gap over the sampled answers (see
    :func:`gap_lists`)."""
    return {k: max(v, default=math.inf)
            for k, v in gap_lists(cell, recorder, against).items()
            if k in cell.config["limits"]}


def check(cell: Cell, recorder: Recorder) -> Dict[str, dict]:
    """Each number compared, with its limit from the config file."""
    return {k: {"value": v, "limit": cell.config["limits"][k]}
            for k, v in readings(cell, recorder).items()}


def correct(win: Window, checks: Dict[str, dict]) -> bool:
    """No session failed, some round completed, and every number compared
    lies within its limit; a run that compared nothing is not correct."""
    return bool(win.failed == 0 and win.rounds > 0 and checks and all(
        c["value"] <= c["limit"] for c in checks.values()))
