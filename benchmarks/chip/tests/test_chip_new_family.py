"""A cell of a new model family is added to a copy of the benchmark as new
files and new manifest entries alone: the program's ``moe`` family through
``lm_task`` at a tiny size, a config that lists engine counters, a
reference stub, its operation count, the ``tokens`` dataset, a traffic
mix, and two readers, one of a model-scope share and one of an engine
counter. The harness builds, warms, runs and checks it on the CPU, on one
device and on four forced host devices (``mesh_child.py``); the scope
reader reads a number from a trace recorded on the chip
(``record_scope_trace.py``)."""

import copy
import gzip
import json
import os
import shutil
import subprocess
import sys
import tempfile

import pytest

import harness

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPE_TRACE = os.path.join(HERE, "data", "scope-trace.json.gz")
CELL = "tinymoe-modest"
MODEL = {"arch": "qwen3-moe-30b-a3b", "overrides": {
    "n_layers": 1, "d_model": 64, "n_heads": 2, "n_kv_heads": 2,
    "head_dim": 32, "d_ff": 128, "vocab": 128, "moe_num_experts": 4,
    "moe_d_ff_expert": 64}}
CONFIG = {
    "name": "tinymoe",
    "source": "a test configuration",
    "task": {"factory": "lm_task",
             "overrides": {"arch": MODEL["arch"], "reduce": True,
                           **MODEL["overrides"]}},
    "model": {"family": "tinymoe", **MODEL, "matmul_operands": "float32"},
    "train": {"optimizer": "sgd", "lr": 0.05, "batch_size": 4},
    "dataset": {"kind": "tokens", "seq_len": 16, "vocab": 128,
                "per_node": 8, "test": 64, "topics": 4, "skew": 0.8},
    "counters": ["jobs_served", "no_such_counter"],
    "limits": {"train_gap": 1e-3, "agg_gap": 1e-3, "eval_loss_gap": 1e-4},
}
STUB_REFERENCE = '''
"""A stub reference of the tests' tiny MoE: SGD and evaluation through the
program's own model. (A real cell's reference imports nothing of the
program.)"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from refcommon import batches, optimizer


@functools.lru_cache(maxsize=None)
def _model(arch, overrides):
    from repro.configs import get_config, reduced
    from repro.models import build

    return build(reduced(get_config(arch)).with_(**dict(overrides)))


def _nest(flat):
    tree = {}
    for path, v in flat.items():
        *head, last = path.split("/")
        node = tree
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return tree


def _loss(m, p, x, y, mask):
    batch = {"tokens": x, "labels": y,
             "mask": jnp.broadcast_to(mask[:, None], x.shape)}
    return m.loss_fn(_nest(p), batch)


def train(params, x, y, *, batch_size, epochs, seed, train_cfg, model_cfg,
          dtype="float32", rows=None):
    m = _model(model_cfg["arch"],
               tuple(sorted(model_cfg["overrides"].items())))
    dt = jnp.dtype(dtype)
    update = optimizer(train_cfg, dt)

    @jax.jit
    def step(p, state, xb, yb, mb):
        g = jax.grad(lambda q: _loss(m, q, xb, yb, mb)[0])(p)
        return update(p, g, state)

    p = {k: jnp.asarray(v, dt) for k, v in params.items()}
    state = {k: jnp.zeros_like(v) for k, v in p.items()}
    for xb, yb, mb in batches(x, y, batch_size, seed=seed, epochs=epochs,
                              rows=rows):
        p, state = step(p, state, xb, yb, jnp.asarray(mb, dt))
    return {k: np.asarray(v, np.float32) for k, v in p.items()}


def evaluate(params, x, y, *, model_cfg, dtype="float32", block=64):
    m = _model(model_cfg["arch"],
               tuple(sorted(model_cfg["overrides"].items())))
    p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
    total = 0.0
    for lo in range(0, len(x), block):
        xb, yb = x[lo:lo + block], y[lo:lo + block]
        _, metrics = _loss(m, p, xb, yb, jnp.ones(len(xb), dtype))
        total += float(metrics["loss"]) * len(xb)
    return {"loss": total / len(x)}
'''
STUB_COUNTS = '''
def forward_macs(model):
    """A stand-in count: the tests read no number from it."""
    o = model["overrides"]
    return 16 * o["d_model"] * (o["d_ff"] + o["vocab"])
'''
SCOPE_READER = '''
"""Share of the cohort step's device time under the model scope
``mixer``."""


def read(run):
    t = run.window.program
    return None if t is None else t.under_share("mixer")
'''
COUNTER_READER = '''
"""Results the engine of the traced session answered from a flush (its
``jobs_served`` counter, which the config lists)."""


def read(run):
    s = run.window.traced
    n = None if s is None else s.counters.get("jobs_served")
    return None if n is None else float(n)
'''


def add_cell(root) -> dict:
    """Copy the benchmark under ``root`` and add the stub cell as new files
    and entries; returns ``{path: bytes}`` of the files that were there."""
    chip = root / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    files = {
        "configs/tinymoe.json": json.dumps(CONFIG),
        "reference/tinymoe.py": STUB_REFERENCE,
        "counts/tinymoe.py": STUB_COUNTS,
        "layers/mixer_share.py": SCOPE_READER,
        "layers/stub_jobs_served.py": COUNTER_READER,
    }
    traffic = harness.load_json("traffic", "modest-diurnal-n100")
    files["traffic/modest-tokens.json"] = json.dumps(traffic)
    for rel, text in files.items():
        path = chip / rel
        assert not path.exists(), rel
        path.write_text(text)
    m = copy.deepcopy(harness.manifest())
    m["configs"].append({"name": "tinymoe", "source": "a test",
                         "file": "benchmarks/chip/configs/tinymoe.json",
                         "reduced": [], "why": "a test configuration"})
    m["workloads"].append({"name": CELL, "config": "tinymoe",
                           "traffic": "modest-tokens", "chips": 1,
                           "why": "a test cell"})
    for name, unit, source, layer in (
            ("mixer_share", "%", "device_trace", "model"),
            ("stub_jobs_served", "jobs", "program_counter", "engine")):
        m["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                               "source": source, "layer": layer,
                               "moves": "wall_s_per_round",
                               "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return before


def scope_trace():
    """The chip-recorded scope trace as planes, and the compiled text of
    its step program."""
    from test_chip_program_trace import _ev, _plane

    with gzip.open(SCOPE_TRACE, "rt") as f:
        doc = json.load(f)
    planes = [_plane(p["name"], **{
        line["name"]: [_ev(n, s, d, **({"hlo_module": m} if m else {}))
                       for n, s, d, m in line["events"]]
        for line in p["lines"]}) for p in doc["planes"]]
    return doc, planes


@pytest.fixture(scope="module")
def stub(tmp_path_factory):
    root = tmp_path_factory.mktemp("stub") / "repo"
    before = add_cell(root)
    return root, before


def test_the_cell_is_files_and_entries(stub):
    root, before = stub
    chip = root / "benchmarks" / "chip"
    assert all(p.read_bytes() == b for p, b in before.items())
    m = harness.manifest(str(root))
    assert set(harness.readers(m, CELL, str(chip))) == {
        "mixer_share", "stub_jobs_served"}


def test_the_cell_runs_and_checks_on_one_device(stub):
    """Built, warmed, run with its second session traced, and checked, as
    ``run_cell.py`` does after finding the chip; the counter reader reads
    the engine's count, and the scope reader finds no device trace on the
    CPU."""
    import counting
    import tiny
    from run_cell import Run

    root, _ = stub
    chip = str(root / "benchmarks" / "chip")
    config, traffic = tiny.tiny(CELL, str(root))
    cell = harness.build_cell(CELL, config, traffic, 2**31 + 77, base=chip)
    assert type(cell.task).__name__ == "JaxTask"
    assert cell.task.cfg.family == "moe" and cell.task.cfg.d_model == 64
    compiles = harness.CompileCounter()
    harness.warm_shapes(cell)
    harness.run_session(cell, -1, harness.Recorder(cell.seed),
                        traffic["warmup_seconds"])
    recorder = harness.Recorder(cell.seed, harness.sample_counts(config))
    with tempfile.TemporaryDirectory() as trace_dir:
        win = harness.run_window(cell, 1.0, recorder, compiles,
                                 trace_dir=trace_dir)
    assert win.failed == 0 and win.rounds > 0 and win.compiles == 0
    checks = harness.check(cell, recorder)
    assert set(checks) == {"train_gap", "agg_gap", "eval_loss_gap"}
    assert harness.correct(win, checks), checks
    counters = win.traced.counters
    assert set(counters) == {"jobs_served", "no_such_counter"}
    assert counters["no_such_counter"] is None
    assert counters["jobs_served"] == win.traced.jobs_served > 0
    run = Run(win, counting.train_flops_per_sample(config["model"], chip),
              counting.peaks("TPU v5 lite"), cell.task.flat_spec.n)
    read = harness.readers(harness.manifest(str(root)), CELL, chip)
    assert read["stub_jobs_served"](run) == counters["jobs_served"]
    assert read["mixer_share"](run) is None


def test_the_cell_runs_on_four_forced_devices(stub):
    root, _ = stub
    child = root / "benchmarks" / "chip" / "tests" / "mesh_child.py"
    out = root / "mesh.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(child), "4", str(out), CELL],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-4000:])
    doc = json.loads(out.read_text())
    assert len(doc["devices"]) == 4
    assert set(doc["engines"]) == {"MeshEngine"}
    assert doc["window_compiles"] == 0, doc["compiled"]
    assert doc["failed"] == 0 and doc["rounds"] > 0
    assert doc["correct"], doc["checks"]
    assert doc["counters"]["jobs_served"] > 0
    assert doc["readings"]["stub_jobs_served"] == \
        doc["counters"]["jobs_served"]


def test_the_scope_reader_on_a_trace_recorded_on_the_chip(stub):
    """The stub's scope reader reads the share of the step under one of
    its two inner scopes from a chip trace, and the two shares lie in
    (0, 100) and add up to no more than the step."""
    import program_trace as pt
    from run_cell import Run

    root, _ = stub
    chip = str(root / "benchmarks" / "chip")
    doc, planes = scope_trace()
    t = pt.reduce_program(planes, pt.hlo_op_scopes(doc["texts"]))
    assert t is not None and t.step_ns > 0
    shares = {s: t.under_share(s) for s in doc["scopes"]}
    assert all(v is not None and 0 < v < 100 for v in shares.values())
    assert sum(shares.values()) <= 100
    read = harness.load_module("layers", "mixer_share", chip).read
    assert read(Run(harness.Window(program=t), 0.0, {}, 1)) == \
        shares["mixer"]
