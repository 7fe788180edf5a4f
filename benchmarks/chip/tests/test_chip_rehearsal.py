"""Each cell's traffic at a tiny size on the CPU, through the harness's
own functions (the command itself refuses a CPU): the public calls and
counters the harness reads exist, every round gets one wall time, the
window's answers agree with the plain reference, and the control (the
reference computed in bfloat16 in the program's place) does not."""

import math

import numpy as np
import pytest

import harness
import tiny

CELLS = [w["name"] for w in harness.manifest()["workloads"]]


@pytest.fixture(scope="module", params=CELLS)
def rehearsal(request):
    cell = tiny.tiny_cell(request.param)
    win, recorder, checks = tiny.run_tiny(cell)
    return cell, win, recorder, checks


def test_the_public_surface_the_harness_reads(rehearsal):
    cell = rehearsal[0]
    session = harness.new_session(cell, 1)
    engine = session.engine
    for call in ("result", "aggregate", "evaluate_models"):
        assert callable(getattr(engine, call))
    assert engine.flushes == 0 and engine.jobs_run == 0
    assert session.sim.events_processed == 0
    assert session.result.round_times == []


def test_a_window_is_sound(rehearsal):
    cell, win, recorder, checks = rehearsal
    assert win.attempted >= 1 and win.failed == 0 and win.rounds > 0
    assert win.attempted % cell.traffic["pass_sessions"] == 0
    assert len(win.round_gaps()) == win.rounds
    assert all(g >= 0 for g in win.round_gaps())
    for s in win.sessions:
        assert s.flushes > 0 and s.jobs >= s.flushes and s.events > 0
    assert recorder.train.items and recorder.agg.items and \
        recorder.evals.items
    assert set(checks) == set(cell.config["limits"])
    assert harness.correct(win, checks), checks


def test_per_layer_readers(rehearsal):
    cell, win, _, _ = rehearsal
    import counting
    from run_cell import Run

    run = Run(win, counting.train_flops_per_sample(cell.config["model"]),
              counting.peaks("TPU v5 lite"), cell.task.flat_spec.n)
    got = {x["name"]: harness.load_module("layers", x["name"]).read(run)
           for x in harness.manifest()["per_layer"]}
    for k in ("sim_events_per_s", "jobs_per_flush", "train_mfu",
              "window_compiles"):
        assert got[k] is not None and math.isfinite(got[k]), k
    assert got["jobs_per_flush"] >= 1
    # no trace was taken: the trace's readers find nothing to read
    for k in ("idle_share", "host_loop_share", "agg_roofline",
              "train_dev_ms_per_round", "agg_call_ms", "eval_share"):
        assert got[k] is None, k


@pytest.mark.parametrize("kind", ["control", "half_batch"])
def test_a_stand_in_is_not_correct(rehearsal, kind):
    """The control (the reference in bfloat16) and the half-batch fault,
    each put in the program's place, fail one of the cell's numbers."""
    cell, win, recorder, _ = rehearsal
    got = harness.readings(cell, recorder, harness.stand_in(cell, kind))
    over = {k: v for k, v in got.items() if v > cell.config["limits"][k]}
    assert over, got


def test_control_script_reads_every_stand_in(rehearsal):
    import control

    cell, _, recorder, _ = rehearsal
    got = control.read_seed(cell, recorder)
    assert set(got) >= {"program", "control", "half_batch",
                        "exact_train_gap", "train_leaves", "control_leaves",
                        "half_batch_leaves"}
    assert set(got["program"]) == set(cell.config["limits"])
    names = set(harness.leaves(cell.task.init_params(0))) | {
        "_median", "_whole", "_diff"}
    assert set(got["train_leaves"]) <= names
    for kind in ("control", "half_batch"):
        assert got[kind + "_leaves"]["_whole"] > \
            got["train_leaves"]["_whole"]


def test_samples_are_held_on_the_host(rehearsal):
    """Every sampled answer the recorder holds after the window is on the
    host, and the checks read from it what they read from the device's
    copies of the same answers."""
    import types

    import jax
    import jax.numpy as jnp

    from repro.engine.flat import FlatModel

    cell, _, recorder, checks = rehearsal
    assert not recorder.pending
    kept = recorder.train.kept + recorder.agg.kept + recorder.evals.kept + \
        [recorder.longest]
    leaves = jax.tree.leaves([s.item for s in kept], is_leaf=lambda v:
                             isinstance(v, harness.HostModel))
    assert not any(isinstance(v, jax.Array) for v in leaves)
    assert any(isinstance(v, harness.HostModel) for v in leaves)

    def on_device(v):
        if isinstance(v, harness.HostModel):
            return FlatModel(jnp.asarray(v.buffer), v.spec)
        return jnp.asarray(v) if isinstance(v, np.ndarray) else v

    moved = {id(s): types.SimpleNamespace(item=jax.tree.map(
        on_device, s.item, is_leaf=lambda v: isinstance(
            v, harness.HostModel))) for s in kept}
    device = harness.Recorder(cell.seed)
    for name in ("train", "agg", "evals"):
        getattr(device, name).kept = [moved[id(s)] for s in
                                      getattr(recorder, name).kept]
    device.longest = moved[id(recorder.longest)]
    assert harness.check(cell, device) == checks


def test_sample_counts_come_from_the_config(rehearsal):
    cell = rehearsal[0]
    assert harness.sample_counts(cell.config) == {"train": 8, "agg": 8,
                                                  "eval": 4}
    config = dict(cell.config, check={"train": 2, "eval": 1})
    counts = harness.sample_counts(config)
    assert counts == {"train": 2, "agg": 8, "eval": 1}
    r = harness.Recorder(1, counts)
    assert (r.train.k, r.agg.k, r.evals.k) == (2, 8, 1)


def test_the_two_reference_paths_agree_where_both_are_the_cpu(rehearsal):
    """``reference_on``'s chip path (the cell's first device, ``highest``
    matmul precision), driven on a cell whose first device is the host's
    CPU, reads every number compared as the host path does. What it shows
    is the path's plumbing: where both places are the CPU they agree. On a
    TPU the chip's reference reads differently, and ``build_cell`` refuses
    the chip path without an accelerator."""
    import dataclasses

    cell, _, recorder, checks = rehearsal
    assert cell.config.get("reference_device", "cpu") == "cpu"
    on_chip = dataclasses.replace(
        cell, config=dict(cell.config, reference_device="chip"))
    assert harness.check(on_chip, recorder) == checks


def test_engine_counters_are_copied_by_name(rehearsal):
    """The counters a config lists are copied from each session's engine
    by name, ``None`` where the engine has none; a config that lists none
    gets none."""
    import dataclasses

    cell, win, _, _ = rehearsal
    assert all(s.counters == {} for s in win.sessions)
    listed = dataclasses.replace(cell, config=dict(
        cell.config, counters=["flushes", "shard_uploads", "no_such"]))
    stats = harness.run_session(listed, 0, harness.Recorder(cell.seed), 5.0)
    assert stats.counters == {"flushes": stats.flushes,
                              "shard_uploads": stats.shard_uploads,
                              "no_such": None}
    assert stats.flushes > 0
