"""The program's own spans, scopes and counters read from a profiler trace
(``program_trace.py``): on planes built by hand in the layout a TPU trace
has, on a small trace recorded on a TPU v5e by ``record_trace_spans.py``
(``data/cnn-tiny-trace-spans.json.gz``), and through ``trace_session.py``
on the CPU. ``trace_reduce`` reads what it read before whatever spans the
program adds."""

import gzip
import json
import os
from types import SimpleNamespace as NS

import pytest

import program_trace as pt
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def _plane(name, **lines):
    return NS(name=name, lines=[NS(name=k, events=v)
                                for k, v in lines.items()])


BENCH_SPANS = [
    _ev("bench.window", 0, 10000),
    _ev("bench.result", 200, 2600),
    _ev("bench.aggregate", 4100, 400),
    _ev("bench.evaluate", 7000, 1000),
]
PROGRAM_SPANS = [
    _ev("repro.sim.event", 100, 2900),
    _ev("repro.engine.result", 210, 2580),
    _ev("repro.engine.assemble", 300, 1000, jobs=2),   # the flush's batches
    _ev("repro.engine.dispatch", 1300, 300, steps=2),
    _ev("repro.engine.assemble", 1600, 400, jobs=2),   # the group's fill
    _ev("repro.sim.event", 3000, 500),
    _ev("repro.sim.event", 4000, 2000),
    _ev("repro.engine.aggregate", 4150, 300, models=2),
    _ev("repro.engine.evaluate", 7050, 900, models=1),
]
DEVICE = _plane(
    "/device:TPU:0",
    **{"XLA Ops": [
        _ev("%slice.1 = f32[2,150]", 1400, 100,
            tf_op="jit(step)/unpack/slice"),
        _ev("%fusion.2 = f32[2,20,10]", 1500, 800,
            tf_op="jit(step)/grad/vmap(jvp())/dot_general"),
        _ev("%concatenate.3 = f32[2,136672]", 2300, 100,
            tf_op="jit(step)/pack/concatenate"),
        _ev("%fusion.4 = f32[2,136672]", 2400, 100,
            tf_op="jit(step)/optimizer/add"),
        _ev("%copy.5 = f32[2,136672]", 2500, 100),
        _ev("%custom-call.6 = f32[136672]", 4200, 200)],
       "XLA Modules": [_ev("jit_step(7)", 1400, 1200),
                       _ev("jit__fused(8)", 4200, 200)]})
COUNTERS = pt.Counters(flushes=1, jobs_run=2, rounds=2, jobs_served=1,
                       batch_bytes_h2d=3_000_000)


def _planes(program=True):
    host = BENCH_SPANS + (PROGRAM_SPANS if program else [])
    return [_plane("/host:CPU", python=host), DEVICE]


def test_the_seven_numbers_by_hand():
    t = pt.reduce_program(_planes())
    assert t.window == (0, 10000) and t.devices == 1
    # idle: the window less [1400, 2600) and [4200, 4400)
    assert t.idle_ns == 10000 - 1200 - 200
    got = pt.numbers(t, COUNTERS)
    assert got["assembly_ms_per_flush"] == pytest.approx(1400 / 1e6)
    assert got["dispatch_ms_per_flush"] == pytest.approx(300 / 1e6)
    # of the assembly, [300, 1300) is idle and [1600, 2000) busy
    assert got["idle_in_assembly_share"] == pytest.approx(100 * 1000 / 8600)
    # events [100, 3500) and [4000, 6000) less result and aggregate
    assert got["loop_self_share"] == pytest.approx(
        100 * (5400 - 2580 - 300) / 10000)
    # unpack 100 + pack 100 of the step's 1200
    assert got["train_glue_share"] == pytest.approx(100 * 200 / 1200)
    assert pt.scope_coverage(t) == pytest.approx(100 * 1100 / 1200)
    assert got["jobs_served_share"] == 50.0
    assert got["h2d_mb_per_round"] == 1.5


def test_gaps_are_labelled_by_the_innermost_span_of_either_prefix():
    t = pt.reduce_program(_planes())
    assert t.gaps == [("repro.engine.evaluate", 5600),
                      ("repro.sim.event", 1600),
                      ("repro.engine.assemble", 1400)]
    # the benchmark's own labels stay as they were
    assert tr.reduce_planes(_planes()).gaps == [
        ("bench.evaluate", 5600), ("host", 1600), ("bench.result", 1400)]


def test_trace_reduce_reads_the_same_with_the_program_spans():
    assert tr.reduce_planes(_planes()) == tr.reduce_planes(_planes(False))


def test_a_program_without_spans_or_counters_reads_nothing():
    t = pt.reduce_program(_planes(False))
    assert t is not None
    no_counters = pt.Counters(flushes=1, jobs_run=2, rounds=2)
    assert pt.numbers(t, no_counters) == {
        name: None for name in pt.READERS if name != "train_glue_share"} | {
        "train_glue_share": pytest.approx(100 * 200 / 1200)}
    unscoped = _plane("/device:TPU:0", **{
        "XLA Ops": [_ev("%fusion.2 = f32[2]", 1500, 800)],
        "XLA Modules": [_ev("jit_step(7)", 1400, 1200)]})
    t = pt.reduce_program([_plane("/host:CPU", python=BENCH_SPANS),
                           unscoped])
    assert pt.numbers(t, no_counters) == {n: None for n in pt.READERS}
    assert pt.reduce_program([_plane("/host:CPU", python=PROGRAM_SPANS),
                              DEVICE]) is None


def test_scopes_from_the_op_name_path():
    assert pt.scope_of("jit(step)/optimizer/add") == "optimizer"
    assert pt.scope_of("jit(train_scan)/while/body/closed_call/grad/"
                       "vmap(jvp())/add") == "grad"
    assert pt.scope_of("jit(step)/add") == ""


# Lines of compiled step programs for a TPU v5e at two cohort widths:
# the name ``fusion.39`` stands for ops of different scopes in the two.
HLO_S10 = """\
ENTRY %main.2 (buf.1: f32[10,136672]) -> f32[10,136672] {
  %fusion.39 = f32[10,20,1024]{2,1,0:T(8,128)S(1)} fusion(%a, %b), kind=kOutput, calls=%c, metadata={op_name="jit(step)/grad/vmap(transpose(jvp()))/dot_general" stack_frame_id=29}
  %fusion.12 = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) fusion(%key.1), kind=kLoop, calls=%d, metadata={op_name="jit(step)/unpack/slice"}
  ROOT %copy.193 = f32[10,136672]{1,0:T(8,128)} copy(%e)
}"""
HLO_S3 = """\
ENTRY %main.2 (buf.1: f32[3,136672]) -> f32[3,136672] {
  %fusion.39 = f32[3,136672]{1,0:T(8,128)S(1)} fusion(%a), kind=kLoop, calls=%c, metadata={op_name="jit(step)/pack/concatenate"}
}"""


def test_scopes_from_the_compiled_text():
    scopes = pt.hlo_op_scopes([HLO_S10, HLO_S3])
    event = ("%fusion.39 = f32[10,20,1024]{2,1,0:T(8,128)S(1)} fusion("
             "f32[10,20,6]{...} %a, f32[20] %b), kind=kOutput, calls=%c")
    def step(ev):
        return pt._event_scope(ev, scopes).step

    assert step(_ev(event, 0, 1)) == "grad"
    assert step(_ev("%fusion.39 = f32[3,136672]{1,0:T(8,128)S(1)}"
                    " fusion(f32[3] %a)", 0, 1)) == "pack"
    tuple_op = ("%fusion.12 = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) fusion("
                "u32[2]{0:T(128)} %key.1), kind=kLoop")
    assert step(_ev(tuple_op, 0, 1)) == "unpack"
    assert step(_ev("%copy.193 = f32[10,136672]{1,0:T(8,128)} "
                    "copy(%e)", 0, 1)) == ""
    # a name one program gives one scope is found by name alone
    assert step(_ev("%fusion.12 = (u32[1]{0:T(128)}, u32[1]"
                    "{0:T(128", 0, 1)) == "unpack"
    # the trace's own stat wins
    assert step(_ev(event, 0, 1, tf_op="jit(step)/pack/x")) == "pack"


# A step's compiled text in which the compiler made ops with no op_name: a
# copy of the parameter, and the allocation and writes that a
# concatenation of gradient leaves became.
HLO_MADE = """\
ENTRY %main.2 (buf.1: f32[2,8]) -> f32[2,8] {
  %buf.1 = f32[2,8]{1,0} parameter(0), metadata={op_name="buf"}
  %copy.1 = f32[2,8]{1,0:S(1)} copy(%buf.1)
  %slice.2 = f32[2,4]{1,0} slice(%copy.1), slice={[0:2], [0:4]}, metadata={op_name="jit(step)/unpack/slice"}
  %fusion.3 = f32[2,4]{1,0} fusion(%slice.2), kind=kLoop, calls=%c, metadata={op_name="jit(step)/grad/mul"}
  %fusion.4 = f32[2,4]{1,0} fusion(%fusion.3), kind=kLoop, calls=%d, metadata={op_name="jit(step)/grad/add"}
  %custom-call.5 = f32[2,8]{1,0} custom-call(), custom_call_target="AllocateBuffer"
  %dynamic-update-slice.6 = f32[2,8]{1,0} dynamic-update-slice(%custom-call.5, %fusion.3, %c0, %c0)
  %dynamic-update-slice.7 = f32[2,8]{1,0} dynamic-update-slice(%dynamic-update-slice.6, %fusion.4, %c0, %c4)
  ROOT %fusion.8 = f32[2,8]{1,0} fusion(%dynamic-update-slice.7, %copy.1), kind=kLoop, calls=%e, metadata={op_name="jit(step)/optimizer/add"}
}"""


def test_compiler_made_ops_take_the_scope_of_their_data():
    got = {k.split(" = ")[0]: v.step for k, v in
           pt._program_scopes(HLO_MADE).items()}
    assert got == {"%buf.1": "unpack", "%copy.1": "unpack",
                   "%slice.2": "unpack", "%fusion.3": "grad",
                   "%fusion.4": "grad", "%custom-call.5": "pack",
                   "%dynamic-update-slice.6": "pack",
                   "%dynamic-update-slice.7": "pack",
                   "%fusion.8": "optimizer"}


SPANS_TRACE = os.path.join(HERE, "data", "cnn-tiny-trace-spans.json.gz")


def _spans_trace():
    with gzip.open(SPANS_TRACE, "rt") as f:
        doc = json.load(f)
    planes = []
    for p in doc["planes"]:
        lines = {}
        for line in p["lines"]:
            evs = []
            for n, s, d, extra, *scope in line["events"]:
                if isinstance(extra, dict):           # a host span's stats
                    evs.append(_ev(n, s, d, **extra))
                    continue
                stats = {"hlo_module": extra} if extra else {}
                if scope and scope[0]:
                    stats["tf_op"] = scope[0]
                evs.append(_ev(n, s, d, **stats))
            lines[line["name"]] = evs
        planes.append(_plane(p["name"], **lines))
    return doc, planes


def test_a_trace_recorded_on_the_chip_reads_all_seven():
    doc, planes = _spans_trace()
    t = pt.reduce_program(planes)
    assert t is not None and t.devices == 1
    got = pt.numbers(t, pt.Counters(**doc["counters"]))
    assert all(v is not None and v > 0 for v in got.values()), got
    for name in ("idle_in_assembly_share", "loop_self_share",
                 "train_glue_share", "jobs_served_share"):
        assert got[name] <= 100, (name, got[name])
    # the program's spans nest inside the benchmark's
    assert t.total_ns("repro.engine.assemble") + \
        t.total_ns("repro.engine.dispatch") <= t.total_ns("bench.result")
    labels = [n for n, _ in t.gaps]
    assert any(n.startswith("repro.") for n in labels)
    assert all(n == "host" or n.startswith(pt.PREFIXES) for n in labels)
    # and leave the benchmark's reduction as it reads without them
    r = tr.reduce_planes(planes)
    assert r is not None and r.span_ns("bench.result") > 0
    assert all(n == "host" or n.startswith(tr.SPAN_PREFIX) for n, _ in r.gaps)


def test_trace_session_on_the_cpu():
    """``trace_session.py``'s sessions, counters and costs, on the tests' tiny
    cell; with no TPU plane in the trace the program's numbers are not
    read."""
    import harness
    import tiny
    import trace_session

    cell = tiny.tiny_cell("cnn-modest-diurnal")
    harness.warm_shapes(cell)
    out = trace_session.run(cell, 5)
    assert len(out["walls_s"]) == 4 and len(out["traced"]) == 2
    first, second = out["traced"]
    assert first["counters"] == second["counters"]
    c = first["counters"]
    assert 0 < c["jobs_served"] <= c["jobs_run"] and c["flushes"] > 0
    assert c["batch_bytes_h2d"] > 0 and c["rounds"] > 0
    assert first["compiled_programs"] >= 1
    assert first["seconds"]["xplane_bytes"] > 0
    # the CPU's trace has no TPU plane: the reductions find nothing
    assert "numbers" not in first and "bench" not in first
    json.dumps(out)


def test_the_seven_readers_on_the_recorded_trace():
    """The benchmark's readers ``layers/<name>.py`` read from a window what
    :func:`program_trace.numbers` reads from the same trace and counters."""
    import harness
    from run_cell import Run

    doc, planes = _spans_trace()
    t = pt.reduce_program(planes)
    c = doc["counters"]
    window = harness.Window(program=t, traced=harness.SessionStats(
        rounds=c["rounds"], flushes=c["flushes"], jobs=c["jobs_run"],
        jobs_served=c["jobs_served"], batch_bytes_h2d=c["batch_bytes_h2d"]))
    run = Run(window, 0.0, {}, doc["n_params"])
    want = pt.numbers(t, pt.Counters(**c))
    got = {name: harness.load_module("layers", name).read(run)
           for name in pt.READERS}
    assert got == want and all(v is not None for v in got.values())
    assert all(harness.load_module("layers", name).read(
        Run(harness.Window(), 0.0, {}, 1)) is None for name in pt.READERS)


@pytest.mark.parametrize("op_name, model", [
    ("jit(step)/grad/vmap(jvp(attn))/dot_general", "attn"),
    ("jit(step)/grad/vmap(transpose(jvp()))/while/body/closed_call/"
     "checkpoint/rematted_computation/attn/dot_general", "attn"),
    ("jit(step)/grad/vmap(transpose(jvp()))/while/body/closed_call/"
     "checkpoint/experts/inner/transpose", "experts/inner"),
    ("jit(step)/grad/vmap(jvp(attn))/jit(relu)/max", "attn"),
    ("jit(step)/grad/vmap(transpose(grad))/vmap(jvp(attn))/select_n",
     "attn"),
    ("jit(step)/grad/vmap(jvp())/cond/branch_1_fun/mixer/mul", "mixer"),
    ("jit(step)/grad/vmap(jvp(experts))/gecd,edf->gecf/dot_general",
     "experts/gecd,edf->gecf"),
    ("jit(train_scan)/while/body/closed_call/grad/vmap(jvp(mla))/add",
     "mla"),
    ("jit(step)/grad/vmap(jvp(attn))/reshape;jit(step)/unpack/reshape",
     "attn"),
    ("jit(step)/pack/reshape;jit(step)/grad/vmap(jvp(attn))/reshape", ""),
    ("jit(step)/grad/vmap(jvp(experts))/gecd,edf->gecf/transpose;"
     "gecd,gtec->gtd/dot_general", "experts/gecd,edf->gecf"),
    ("jit(step)/add;grad/vmap(jvp(attn))/mul", ""),
    ("jit(step)/grad/vmap(jvp())/mul", ""),
    ("jit(step)/optimizer/add", ""),
    ("jit(step)/mixer/add", ""),            # no step scope above it
])
def test_model_scopes_from_the_op_name_path(op_name, model):
    assert pt.model_scope_of(op_name) == model
    assert pt.op_scope(op_name) == pt.OpScope(pt.scope_of(op_name), model)


# A step's compiled text with model scopes: a copy the compiler made
# between ``unpack`` and the ``mixer`` fusion, a fusion of ops of both
# model scopes that carries its root's ``op_name`` (``experts``), and the
# writes of the gradient into the buffer the optimizer reads.
HLO_MODEL = """\
ENTRY %main.2 (buf.1: f32[2,8]) -> f32[2,8] {
  %buf.1 = f32[2,8]{1,0} parameter(0), metadata={op_name="buf"}
  %slice.2 = f32[2,4]{1,0} slice(%buf.1), slice={[0:2], [0:4]}, metadata={op_name="jit(step)/unpack/slice"}
  %copy.3 = f32[2,4]{1,0:S(1)} copy(%slice.2)
  %fusion.4 = f32[2,4]{1,0} fusion(%copy.3), kind=kLoop, calls=%c, metadata={op_name="jit(step)/grad/vmap(jvp(mixer))/tanh"}
  %fusion.5 = f32[2,4]{1,0} fusion(%fusion.4), kind=kOutput, calls=%d, metadata={op_name="jit(step)/grad/vmap(transpose(jvp(experts)))/dot_general"}
  %copy.6 = f32[2,4]{1,0} copy(%fusion.5)
  %custom-call.7 = f32[2,8]{1,0} custom-call(), custom_call_target="AllocateBuffer"
  %dynamic-update-slice.8 = f32[2,8]{1,0} dynamic-update-slice(%custom-call.7, %copy.6, %c0, %c0)
  ROOT %fusion.9 = f32[2,8]{1,0} fusion(%dynamic-update-slice.8, %buf.1), kind=kLoop, calls=%e, metadata={op_name="jit(step)/optimizer/add"}
}"""


def test_compiler_made_and_merged_ops_take_one_model_scope():
    got = {k.split(" = ")[0]: (v.step, v.model) for k, v in
           pt._program_scopes(HLO_MODEL).items()}
    assert got == {"%buf.1": ("unpack", ""), "%slice.2": ("unpack", ""),
                   "%copy.3": ("grad", "mixer"),
                   "%fusion.4": ("grad", "mixer"),
                   "%fusion.5": ("grad", "experts"),
                   "%copy.6": ("pack", ""), "%custom-call.7": ("pack", ""),
                   "%dynamic-update-slice.8": ("pack", ""),
                   "%fusion.9": ("optimizer", "")}


def test_a_bare_name_keeps_its_step_scope_where_model_scopes_differ():
    other = HLO_MODEL.replace("f32[2,4]{1,0} fusion(%fusion.4)",
                              "f32[3,4]{1,0} fusion(%fusion.4)").replace(
        "jvp(experts)", "jvp(mixer)")
    scopes = pt.hlo_op_scopes([HLO_MODEL, other])
    assert scopes["%fusion.5"] == pt.OpScope("grad")
    assert scopes["%fusion.4"] == pt.OpScope("grad", "mixer")


def test_device_time_by_model_scope_by_hand():
    dev = _plane("/device:TPU:0", **{
        "XLA Ops": [
            _ev("%a = f32[2]", 1400, 100, tf_op="jit(step)/unpack/slice"),
            _ev("%b = f32[2]", 1500, 300,
                tf_op="jit(step)/grad/vmap(jvp(mixer))/tanh"),
            _ev("%c = f32[2]", 1800, 400,
                tf_op="jit(step)/grad/vmap(jvp(experts))/up/dot_general"),
            _ev("%d = f32[2]", 2200, 200,
                tf_op="jit(step)/grad/vmap(jvp(experts))/dot_general"),
            _ev("%e = f32[2]", 2400, 200, tf_op="jit(step)/optimizer/add"),
            _ev("%f = f32[2]", 4200, 200,
                tf_op="jit(_fused)/mixer/add")],       # not a step program
        "XLA Modules": [_ev("jit_step(7)", 1400, 1200),
                        _ev("jit__fused(8)", 4200, 200)]})
    t = pt.reduce_program([_plane("/host:CPU", python=BENCH_SPANS), dev])
    assert t.model_scope_ns == {"": 300, "mixer": 300, "experts/up": 400,
                                "experts": 200}
    assert t.step_ns == 1200
    assert t.under_ns("experts") == 600 and t.under_ns("up") == 400
    assert t.under_share("mixer") == pytest.approx(25.0)
    assert t.under_share("experts") == pytest.approx(50.0)
    assert t.under_share("absent") is None


CHIP_TRACES = {
    "cnn-tiny-trace-spans.json.gz": {
        "unpack": 69913.0, "grad": 18413262.0, "pack": 211869.0,
        "optimizer": 101124.0},
    "cnn-tiny-trace.json.gz": {"": 18799946.0},
}


@pytest.mark.parametrize("name", sorted(CHIP_TRACES))
def test_the_step_scopes_of_the_recorded_traces_read_as_before(name):
    """Every trace recorded on the chip before the model scopes came reads
    the same step scopes (the numbers its reduction gave then), and puts
    all of the step's time under no model scope: it kept no path."""
    with gzip.open(os.path.join(HERE, "data", name), "rt") as f:
        doc = json.load(f)
    planes = []
    for p in doc["planes"]:
        lines = {}
        for line in p["lines"]:
            evs = []
            for n, s, d, extra, *scope in line["events"]:
                stats = {"hlo_module": extra} if isinstance(extra, str) \
                    and extra else {}
                if scope and scope[0]:
                    stats["tf_op"] = scope[0]
                evs.append(_ev(n, s, d, **stats))
            lines[line["name"]] = evs
        planes.append(_plane(p["name"], **lines))
    t = pt.reduce_program(planes)
    assert t.scope_ns == CHIP_TRACES[name]
    assert t.model_scope_ns == {"": t.step_ns}
    if "grad" in t.scope_ns:
        assert pt.train_glue_share(t, None) == 100 * (69913 + 211869) / \
            sum(CHIP_TRACES[name].values())


def _compiled_paths(fn, *args) -> set:
    texts = [fn.lower(*args).compile().as_text()]
    return {v.model for v in pt.hlo_op_scopes(texts).values()}


def test_no_label_of_jax_reads_as_a_model_scope():
    """A step compiled on the CPU through a scan, rematerialisation, a
    cond, a custom-JVP function and a nested jit, under two named scopes:
    only those two are found; and the program's tiny MoE step, which has
    no scope of its own, shows only the names ``jnp.einsum`` gives its
    contractions."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def helper(h):
        return jnp.sin(h)

    def model(p, x):
        with jax.named_scope("mixer"):
            h = jax.nn.relu(x @ p["a"])
            h = jax.lax.cond(h.sum() > 0, lambda h: h * 2, lambda h: h, h)
        with jax.named_scope("experts"):
            h = helper(h @ p["b"])
        return jnp.sum(h ** 2)

    def loss(p, xs):
        body = jax.checkpoint(lambda c, x: (c + model(p, x), None))
        return jax.lax.scan(body, 0.0, xs)[0]

    def step(p, x):
        with jax.named_scope("grad"):
            g = jax.vmap(jax.grad(loss))(p, x)
        with jax.named_scope("optimizer"):
            return jax.tree.map(lambda a, b: a - 0.1 * b, p, g)

    p = {"a": jnp.ones((2, 8, 8)), "b": jnp.ones((2, 8, 8))}
    assert _compiled_paths(jax.jit(step), p, jnp.ones((2, 3, 4, 8))) == {
        "", "mixer", "experts"}

    from repro.config import TrainConfig
    from repro.engine.cohort import _cohort_ops
    from repro.models.tasks import lm_task

    task = lm_task("qwen3-moe-30b-a3b", tcfg=TrainConfig(lr=0.05),
                   n_layers=1, d_model=64, n_heads=2, n_kv_heads=2,
                   head_dim=32, vocab=128, moe_d_ff_expert=64)
    opt, step_fn, _ = _cohort_ops(task)
    buf = jnp.zeros((2, task.flat_spec.n))
    tokens = jnp.zeros((2, 2, 16), jnp.int32)
    paths = _compiled_paths(step_fn, buf, opt.init(buf), tokens, tokens,
                            jnp.ones((2, 2)), jnp.ones(2, bool))
    assert "" in paths and len(paths) > 1
    assert all("->" in part for path in paths for part in path.split("/")
               if part), paths
