"""With the timed path broken underneath, the harness's check comes out
not correct: once for each fault a cell can have on one chip. (A cell on
one chip has no exchange between chips to leave out.)"""

import jax.numpy as jnp
import pytest

import harness
import tiny
from repro.engine import cohort
from repro.engine.flat import FlatModel
from repro.models.tasks import JaxTask

CELLS = [w["name"] for w in harness.manifest()["workloads"]]


def _state_unchanged(monkeypatch):
    """The train step returns its parameters and state unchanged."""
    real = cohort._cohort_ops

    def ops(task, shardings=None):
        opt, _, _ = real(task, shardings)
        return (opt, lambda buf, state, *a: (buf, state),
                lambda buf, state, *a: buf)

    monkeypatch.setattr(cohort, "_cohort_ops", ops)
    monkeypatch.setattr(JaxTask, "local_train",
                        lambda self, params, *a, **k: params)


def _half_batch(monkeypatch):
    """Half of each batch is left out; the mean is taken over the rest."""
    real = cohort.masked_loss_for

    def masked_loss_for(task):
        loss = real(task)

        def half(params, batch):
            m = batch["mask"]
            keep = jnp.arange(m.shape[0]) < m.shape[0] // 2
            return loss(params, dict(batch, mask=m * keep))

        return half

    monkeypatch.setattr(cohort, "masked_loss_for", masked_loss_for)


def _aggregate_altered(monkeypatch):
    """One parameter of each aggregated model is altered where the
    aggregate is produced."""
    real = JaxTask.aggregate

    def aggregate(self, models, weights=None, **kw):
        out = real(self, models, weights, **kw)
        return FlatModel(out.buffer.at[0].add(1.0), out.spec)

    monkeypatch.setattr(JaxTask, "aggregate", aggregate)


def _evaluation_altered(monkeypatch):
    """Each evaluated loss is altered where the evaluation is produced."""
    real = JaxTask.evaluate_many

    def evaluate_many(self, models, test):
        return [dict(m, loss=m["loss"] * 1.1)
                for m in real(self, models, test)]

    monkeypatch.setattr(JaxTask, "evaluate_many", evaluate_many)


def _one_slot_unchanged(monkeypatch):
    """The last slot of every cohort group returns its job's parameters
    unchanged, the other slots train: a fault that reaches only some of
    the jobs (one vmap slot, or a whole group of one job)."""
    real = cohort.BatchedEngine._run_group

    def run_group(self, pairs):
        real(self, pairs)
        job = pairs[-1][0]
        out, *rest = self._done[job.key]
        self._done[job.key] = (
            FlatModel(cohort.as_buffer(job.params, self.spec), out.spec),
            *rest)

    monkeypatch.setattr(cohort.BatchedEngine, "_run_group", run_group)


FAULTS = {"state_unchanged": (_state_unchanged, "train_gap"),
          "half_batch": (_half_batch, "train_gap"),
          "one_slot_unchanged": (_one_slot_unchanged, "train_gap"),
          "aggregate_altered": (_aggregate_altered, "agg_gap"),
          "evaluation_altered": (_evaluation_altered, "eval_loss_gap")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    plant, number = FAULTS[fault]
    plant(monkeypatch)
    cell = tiny.tiny_cell(name)
    win, _, checks = tiny.run_tiny(cell, seconds=0.5)
    assert not harness.correct(win, checks)
    assert checks[number]["value"] > checks[number]["limit"], checks


def test_a_run_that_compares_nothing_is_not_correct():
    win = harness.Window(sessions=[harness.SessionStats(round_walls=[1.0])])
    assert not harness.correct(win, {})
    assert harness.correct(win, {"train_gap": {"value": 0.0, "limit": 0.08}})
