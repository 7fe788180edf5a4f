"""The program's own trace spans and device scopes (``repro.utils.spans``):
a tiny batched session run under ``jax.profiler`` on the CPU writes every
host span, nested as the engine's calls nest, one ``repro.sim.event`` per
processed event; the compiled cohort step names its ops by scope."""

import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.config import ModestConfig, TrainConfig
from repro.engine.cohort import _cohort_ops
from repro.models.tasks import cnn_task
from repro.utils import spans


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(host spans as ``(name, start, end, stats)`` per thread line, the
    session) of a tiny Plexus session traced from start to end."""
    from jax.profiler import ProfileData

    from repro.data import make_classification_task
    from repro.sim.runner import ModestSession

    n = 6
    data = make_classification_task(n, samples_per_node=30, iid=False,
                                    alpha=0.5, seed=0)
    mcfg = ModestConfig(n_nodes=n, sample_size=3, n_aggregators=2,
                        success_fraction=1.0, ping_timeout=1.0)
    session = ModestSession(n_nodes=n, mcfg=mcfg,
                            tcfg=TrainConfig(batch_size=20), task=cnn_task(),
                            data=data, seed=0, eval_every_rounds=2,
                            engine="batched")
    out = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(out):
        session.run(12.0)
    path, = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                       for ev in line.events
                       if ev.name.startswith("repro.")]
                if evs:
                    lines.append(evs)
    return lines, session


def _named(lines, name):
    return [ev for line in lines for ev in line if ev[0] == name]


def _inside(ev, line, name):
    """The span called ``name`` on the same thread that holds ``ev``."""
    return [o for o in line if o[0] == name and o[1] <= ev[1]
            and ev[2] <= o[2]]


def test_every_host_span_is_written(traced):
    lines, session = traced
    names = {ev[0] for line in lines for ev in line}
    assert names == set(spans.HOST_SPANS)
    assert session.engine.flushes > 0


def test_one_event_span_per_processed_event(traced):
    lines, session = traced
    assert len(_named(lines, spans.SIM_EVENT)) == \
        session.sim.events_processed


def test_flush_spans_nest_in_result_in_event(traced):
    lines, _ = traced
    nested = 0
    for line in lines:
        for ev in line:
            if ev[0] not in (spans.ENGINE_ASSEMBLE, spans.ENGINE_DISPATCH):
                continue
            results = _inside(ev, line, spans.ENGINE_RESULT)
            assert len(results) == 1, ev
            assert _inside(results[0], line, spans.SIM_EVENT), ev
            nested += 1
    assert nested == len(_named(lines, spans.ENGINE_ASSEMBLE)) + \
        len(_named(lines, spans.ENGINE_DISPATCH)) > 0


def test_spans_carry_their_sizes(traced):
    lines, session = traced
    assemble = _named(lines, spans.ENGINE_ASSEMBLE)
    assert all(ev[3]["jobs"] >= 1 for ev in assemble)
    # one span around the flush's batch building, one per group's fill
    assert sum(ev[3]["jobs"] for ev in assemble) >= \
        2 * session.engine.jobs_run > 0
    assert all(ev[3]["steps"] >= 1
               for ev in _named(lines, spans.ENGINE_DISPATCH))
    for name in (spans.ENGINE_AGGREGATE, spans.ENGINE_EVALUATE):
        assert all(ev[3]["models"] >= 1 for ev in _named(lines, name))


def test_cohort_step_ops_are_scoped():
    """Each scope names ops of the compiled step and of the scan that
    reuses it, in the ``op_name`` metadata the device trace carries."""
    task = cnn_task()
    opt, step, scan = _cohort_ops(task)
    s, n = 2, task.flat_spec.n
    buf = jax.ShapeDtypeStruct((s, n), jnp.float32)
    state = jax.eval_shape(opt.init, buf)
    x = jax.ShapeDtypeStruct((s, 20, 32, 32, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((s, 20), jnp.int32)
    m = jax.ShapeDtypeStruct((s, 20), jnp.float32)
    act = jax.ShapeDtypeStruct((s,), jnp.bool_)

    def stacked(a, t=3):
        return jax.ShapeDtypeStruct((t,) + a.shape, a.dtype)

    for low in (step.lower(buf, state, x, y, m, act),
                scan.lower(buf, state, stacked(x), stacked(y), stacked(m),
                           stacked(act))):
        text = low.as_text(debug_info=True)
        for scope in spans.STEP_SCOPES:
            assert re.search(f'["/]{scope}/', text), scope
