"""FlatModel compute engine: pack/unpack round-trips, whole-model one-pass
aggregation (incl. the fused aggregate→quantize kernel and the ≤2
pallas_call regression guard), vmapped-vs-sequential cohort trajectory
parity, the ragged-tail loss-mask semantics, and session integration."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import ModestConfig, TrainConfig
from repro.data.loader import ClientDataset
from repro.engine import BatchedEngine, FlatModel, FlatSpec, make_engine
from repro.engine.cohort import SequentialEngine
from repro.kernels import aggregate_flatmodel, aggregate_pytree, ref
from repro.kernels.fused import SUBTILE
from repro.models.tasks import cnn_task
from repro.utils.pytree import tree_size_bytes, tree_weighted_mean


@pytest.fixture(scope="module")
def task():
    return cnn_task()


@pytest.fixture(scope="module")
def small_clients():
    rng = np.random.default_rng(0)
    return [ClientDataset(rng.normal(size=(n, 32, 32, 3)).astype(np.float32),
                          rng.integers(0, 10, n))
            for n in (25, 40, 15)]          # ragged, full, tail-only mixes


# ---------------------------------------------------------------- FlatSpec


DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32, jnp.int16]


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4), st.integers(0, 100))
def test_flat_roundtrip_property(leaves, seed):
    """pack → unpack is exact for fp32/bf16/int leaves of any shapes."""
    rng = np.random.default_rng(seed)
    tree = {}
    for i in range(leaves):
        dt = DTYPES[(seed + i) % len(DTYPES)]
        shape = tuple(rng.integers(1, 7, size=rng.integers(0, 3)))
        if jnp.issubdtype(dt, jnp.integer):
            leaf = jnp.asarray(rng.integers(-500, 500, size=shape), dt)
        else:
            leaf = jnp.asarray(rng.normal(size=shape) * 3, dt)
        tree[f"l{i}"] = leaf
    spec = FlatSpec.from_tree(tree)
    fm = FlatModel.pack(tree, spec)
    assert fm.buffer.dtype == jnp.float32
    assert fm.buffer.shape == (spec.n,)
    back = fm.tree
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        assert back[k].shape == tree[k].shape
        np.testing.assert_array_equal(np.asarray(back[k], np.float64),
                                      np.asarray(tree[k], np.float64))


def test_flat_wire_bytes_match_tree(task):
    """Byte accounting is representation-independent: a FlatModel reports
    the original pytree's size, not its fp32 working buffer's."""
    params = task.init_params(0)
    fm = FlatModel.pack(params, task.flat_spec)
    assert tree_size_bytes(fm) == tree_size_bytes(params)
    assert task.model_bytes() == tree_size_bytes(params)


def test_unpack_rounds_integer_leaves():
    tree = {"step": jnp.asarray([7, -3], jnp.int32)}
    spec = FlatSpec.from_tree(tree)
    buf = jnp.asarray([6.6, -3.4], jnp.float32)
    out = spec.unpack(buf)
    assert out["step"].tolist() == [7, -3]        # round, not truncate


# ------------------------------------------------------------- aggregation


def test_aggregate_flatmodel_matches_reference(task):
    params = task.init_params(0)
    models = [jax.tree.map(lambda l: l + 0.1 * i, params) for i in range(4)]
    w = [0.5, 1.0, 2.0, 0.25]
    got = aggregate_flatmodel(models, w, spec=task.flat_spec).tree
    want = tree_weighted_mean(models, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_aggregate_flatmodel_integer_leaves(use_kernel):
    models = [{"w": jnp.ones((300,)), "step": jnp.asarray([7, 100], jnp.int32)},
              {"w": jnp.zeros((300,)), "step": jnp.asarray([8, 101], jnp.int32)}]
    got = aggregate_flatmodel(models, [1.0, 1.0], use_kernel=use_kernel).tree
    assert got["step"].dtype == jnp.int32
    assert got["step"].tolist() == [8, 100]       # round-half-even, not floor
    np.testing.assert_allclose(np.asarray(got["w"]), 0.5)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_fused_aggregate_quantize_matches_ref(task, use_kernel):
    """Fused agg→quantize codes/scales == quantize_ref(mean), any tiling."""
    params = task.init_params(0)
    models = [jax.tree.map(lambda l: l + 0.01 * i, params) for i in range(3)]
    w = [1.0, 2.0, 0.5]
    fm, codes, scales = aggregate_flatmodel(models, w, spec=task.flat_spec,
                                            quantize=True,
                                            use_kernel=use_kernel)
    n = task.flat_spec.n
    pad = (-n) % SUBTILE
    want_q, want_s = ref.quantize_ref(jnp.pad(fm.buffer, (0, pad)))
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(want_q[:n]))
    np.testing.assert_allclose(np.asarray(scales),
                               np.asarray(want_s[: len(scales)]), rtol=1e-6)


def test_zero_weight_raises_everywhere(task):
    """Satellite: the zero-weight contract is a raise on every path."""
    params = task.init_params(0)
    models = [params, params]
    with pytest.raises(ValueError):
        tree_weighted_mean(models, [0.0, 0.0])
    with pytest.raises(ValueError):
        aggregate_pytree(models, [0.0, 0.0])
    with pytest.raises(ValueError):
        aggregate_flatmodel(models, [0.0, 0.0])
    with pytest.raises(ValueError):
        task.aggregate(models, [0.0, -0.0])


def test_onepass_kernel_count(task, monkeypatch):
    """Whole-model aggregation must issue ≤2 Pallas kernel launches per
    model batch (the per-leaf path issues one per leaf — 7 for the paper
    CNN). Counted at the launch-wrapper layer: each wrapper contains
    exactly one ``pallas_call``."""
    import repro.kernels.ops as ops

    counts = {"leaf": 0, "one": 0, "oneq": 0}
    real_tiles = ops.aggregate_tiles
    real_one = ops.aggregate_flat_onepass
    real_oneq = ops.aggregate_quantize_flat

    def count(key, real):
        def f(*a, **k):
            counts[key] += 1
            return real(*a, **k)
        return f

    monkeypatch.setattr(ops, "aggregate_tiles", count("leaf", real_tiles))
    monkeypatch.setattr(ops, "aggregate_flat_onepass",
                        count("one", real_one))
    monkeypatch.setattr(ops, "aggregate_quantize_flat",
                        count("oneq", real_oneq))

    params = task.init_params(0)
    models = [params, jax.tree.map(lambda l: l + 1, params)]
    aggregate_flatmodel(models, [1.0, 1.0], spec=task.flat_spec,
                        use_kernel=True, interpret=True)
    assert counts["one"] == 1 and counts["leaf"] == 0

    aggregate_pytree(models, np.asarray([1.0, 1.0]), interpret=True)
    assert counts["leaf"] == len(task.flat_spec.shapes)   # one per leaf

    # fused aggregate→quantize is still a single launch
    aggregate_flatmodel(models, [1.0, 1.0], spec=task.flat_spec,
                        quantize=True, use_kernel=True, interpret=True)
    assert counts["oneq"] == 1 and counts["one"] == 1


def test_import_initialises_no_backend():
    """Importing the engine, kernels, tasks and sessions must not
    initialise a JAX backend: on a TPU host that would claim the chip
    for a process that may only be a parent of the one that needs it."""
    code = ("import repro.engine, repro.kernels, repro.models.tasks, "
            "repro.sim.runner, repro.launch.mesh\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120,
                          env={**os.environ, "PYTHONPATH": src})
    assert proc.returncode == 0, proc.stderr[-2000:]


# ------------------------------------------------------- cohort training


def _max_err(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_cohort_matches_sequential_fp32(task, small_clients):
    """Batched-vs-sequential trajectory parity on the paper CNN: same
    seeds, ragged client sizes, fp32 tolerance tier."""
    params = task.init_params(0)
    engine = BatchedEngine(task)
    seq = [task.local_train(params, c, batch_size=20, epochs=1, seed=11)
           for c in small_clients]
    for i, c in enumerate(small_clients):
        engine.submit(str(i), 1, params, c, batch_size=20, epochs=1, seed=11)
    got = [engine.result(str(i), 1, params, c, batch_size=20, epochs=1,
                         seed=11)
           for i, c in enumerate(small_clients)]
    # whole cohort ran on the first demand (grouped into step-count
    # buckets: clients with 2 training steps vs the 15-sample 1-stepper)
    assert engine.jobs_run == 3 and engine.flushes == 2
    for s, g in zip(seq, got):
        assert isinstance(g, FlatModel)
        assert _max_err(s, g.tree) < 5e-4


def _fresh(clients):
    """Copies of ``clients`` that no task has cached on the device yet."""
    return [ClientDataset(c.x.copy(), c.y.copy()) for c in clients]


def test_counters_served_and_batch_bytes(task, small_clients):
    """``jobs_served`` counts the result() calls a flush answered: a
    planned job no node claims is trained (``jobs_run``) but never served.
    ``batch_bytes_h2d`` is what the engine copies to the device for
    training: each client's shard once (rows padded to whole batches,
    labels as int32), then per group its indices, masks and active
    flags."""
    clients = _fresh(small_clients)
    params = task.init_params(0)
    engine = BatchedEngine(task)
    for i, c in enumerate(clients):
        engine.register_client(str(i), c)
    # clients 0 and 1 (25 and 40 samples) both take 2 steps: one group
    engine.plan_cohort(1, ["0", "1"], params, batch_size=20, epochs=1,
                       seed=11)
    engine.result("0", 1, params, clients[0], batch_size=20, epochs=1,
                  seed=11)
    assert (engine.flushes, engine.jobs_run, engine.jobs_served) == (1, 2, 1)
    row = clients[0].x[0].nbytes + 4            # an image and its label
    T, S, B = 2, 2, 20
    want = 2 * 40 * row + T * S * B * (4 + 4) + T * S
    assert engine.batch_bytes_h2d == want
    assert engine.shard_uploads == 2
    # a job never planned is trained alone and served: one more group,
    # and the 15-sample client's shard padded to one batch of 20
    engine.result("2", 1, params, clients[2], batch_size=20, epochs=1,
                  seed=11)
    assert (engine.flushes, engine.jobs_run, engine.jobs_served) == (2, 3, 2)
    assert engine.batch_bytes_h2d == want + 20 * row + 1 * 1 * B * 8 + 1
    assert engine.shard_uploads == 3
    # client 1's planned job is never claimed: run, not served
    assert engine.jobs_served < engine.jobs_run


def _spy_batches(engine):
    """Record ``(x, y, mask, active)`` of every step as the step program
    receives them (a scan's ``(T, S, …)`` inputs split into its steps)."""
    steps = []
    step, scan = engine._step, engine._scan

    def spy_step(buf, state, *batch):
        steps.append(batch)
        return step(buf, state, *batch)

    def spy_scan(buf, state, *batches):
        steps.extend(zip(*batches))
        return scan(buf, state, *batches)

    engine._step, engine._scan = spy_step, spy_scan
    return steps


@pytest.mark.parametrize("sizes", [(25, 40), (25,)],
                         ids=["step-form", "scan-form"])
def test_device_gathered_batches_match_padded_batches(task, sizes):
    """The batches the engine gathers on the device are, bit for bit, the
    ones ``_padded_batches`` builds on the host for the same seed: ragged
    shards (a short last batch), two epochs, and two clients of different
    lengths in one step-count group (vmapped together, per-step form) or
    one client alone (scan form)."""
    rng = np.random.default_rng(4)
    clients = [ClientDataset(rng.normal(size=(n, 32, 32, 3))
                             .astype(np.float32), rng.integers(0, 10, n))
               for n in sizes]
    params = task.init_params(0)
    engine = BatchedEngine(task)
    steps = _spy_batches(engine)
    for i, c in enumerate(clients):
        engine.submit(str(i), 1, params, c, batch_size=20, epochs=2,
                      seed=30 + i)
    engine.result("0", 1, params, clients[0], batch_size=20, epochs=2,
                  seed=30)
    assert engine.flushes == 1 and len(steps) == 4
    for s, c in enumerate(clients):
        want = task._padded_batches(c, 20, seed=30 + s, epochs=2)
        assert len(want) == len(steps)
        for (x, y, m, a), (wx, wy, wm) in zip(steps, want):
            np.testing.assert_array_equal(np.asarray(x[s]), wx)
            np.testing.assert_array_equal(np.asarray(y[s]), wy)
            np.testing.assert_array_equal(np.asarray(m[s]), wm)
            assert bool(a[s])


def test_shard_uploaded_once_per_task(task, small_clients):
    """A shard goes to the device once: not again at a second flush, nor
    from a second engine on the same task (sessions build one each); a
    client whose shape changed is uploaded again."""
    c = _fresh(small_clients[:1])[0]
    params = task.init_params(0)
    first = BatchedEngine(task)
    for tag in (1, 2):
        first.result("0", tag, params, c, batch_size=20, epochs=1, seed=tag)
    assert (first.flushes, first.shard_uploads) == (2, 1)
    second = BatchedEngine(task)
    second.result("0", 1, params, c, batch_size=20, epochs=1, seed=5)
    assert (second.flushes, second.shard_uploads) == (1, 0)
    # the bytes of a flush served from resident shards: its plan alone
    assert second.batch_bytes_h2d == 2 * 20 * 8 + 2
    c.x, c.y = np.concatenate([c.x, c.x[:5]]), np.concatenate([c.y, c.y[:5]])
    got = second.result("0", 2, params, c, batch_size=20, epochs=1, seed=6)
    assert second.shard_uploads == 1
    want = task.local_train(params, c, batch_size=20, epochs=1, seed=6)
    assert _max_err(want, got.tree) < 5e-4


def test_cohort_matches_sequential_mf():
    """The gather is blind to the input's dtype and trailing shape: an
    mf-family cohort (int32 ``(user, item)`` pairs, float ratings) of
    ragged shards matches the sequential engine."""
    from repro.data.synthetic import make_mf_task
    from repro.models.tasks import mf_task

    task = mf_task()
    data = make_mf_task(3, n_items=1000, seed=1)
    clients = [ClientDataset(c.x[:n], c.y[:n])
               for c, n in zip(data.clients, (40, 33, 7))]
    params = task.init_params(0)
    engine = BatchedEngine(task)
    for i, c in enumerate(clients):
        engine.submit(str(i), 1, params, c, batch_size=20, epochs=2, seed=i)
    for i, c in enumerate(clients):
        got = engine.result(str(i), 1, params, c, batch_size=20, epochs=2,
                            seed=i)
        want = task.local_train(params, c, batch_size=20, epochs=2, seed=i)
        assert _max_err(want, got.tree) < 1e-5
    # (40, 33) take two steps an epoch, 7 takes one: two groups
    assert (engine.jobs_run, engine.flushes) == (3, 2)


def test_cohort_matches_sequential_bf16(small_clients):
    """bf16 tier: the sequential path re-rounds params to bf16 every step
    while the engine trains in fp32 and rounds once at the boundary, so
    the tolerance is the bf16 resolution, not fp32's."""
    task = cnn_task()
    params = jax.tree.map(lambda l: l.astype(jnp.bfloat16),
                          task.init_params(0))
    engine = BatchedEngine(task)
    seq = task.local_train(params, small_clients[0], batch_size=20,
                           epochs=1, seed=3)
    got = engine.result("0", 1, params, small_clients[0], batch_size=20,
                        epochs=1, seed=3)
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(got.tree))
    assert _max_err(seq, got.tree) < 0.05


def test_cohort_multi_epoch_parity(task, small_clients):
    params = task.init_params(0)
    engine = BatchedEngine(task)
    seq = task.local_train(params, small_clients[0], batch_size=20,
                           epochs=3, seed=5)
    got = engine.result("0", 2, params, small_clients[0], batch_size=20,
                        epochs=3, seed=5)
    assert _max_err(seq, got.tree) < 1e-3


def test_masked_tail_does_not_upweight(task):
    """The ragged tail must contribute each sample once: training on a
    25-sample client (20 + masked 5) equals training on the same batches
    built by hand — and differs from the old replicate-the-tail path."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(25, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, 25)
    client = ClientDataset(x, y)
    params = task.init_params(1)
    batches = task._padded_batches(client, 20, seed=9)
    assert [int(m.sum()) for _, _, m in batches] == [20, 5]
    # manual reference: same step function, explicit masked batches
    opt_state = task._opt.init(params)
    want = params
    for bx, by, bm in batches:
        want, opt_state, _ = task._step(want, opt_state,
                                        task._to_batch(bx, by, bm))
    got = task.local_train(params, client, batch_size=20, seed=9)
    assert _max_err(want, got) < 1e-6
    # replicating the 5 tail samples to fill the batch (the old
    # behaviour) produces a *different* gradient
    bx, by, _ = batches[1]
    reps = np.concatenate([bx[:5]] * 4)[:20]
    ry = np.concatenate([by[:5]] * 4)[:20]
    opt_state = task._opt.init(params)
    old, opt_state, _ = task._step(params, opt_state,
                                   task._to_batch(bx, by,
                                                  np.ones(20, np.float32)))
    assert _max_err(old, got) > 1e-6


def test_cohort_odd_image_shape_falls_back_to_model_lowering():
    """The fast CNN lowering needs spatial dims % 4 == 0; a 30×30 config
    must still train through the batched engine (generic lowering)."""
    t = cnn_task(cnn_image=(20, 20, 3))      # 20 % 4 == 0 -> fast path ok
    t30 = cnn_task(cnn_image=(30, 30, 3))    # 30 % 4 != 0 -> fallback
    rng = np.random.default_rng(0)
    for tk, hw in ((t, 20), (t30, 30)):
        c = ClientDataset(rng.normal(size=(12, hw, hw, 3)).astype(np.float32),
                          rng.integers(0, 10, 12))
        params = tk.init_params(0)
        eng = BatchedEngine(tk)
        got = eng.result("0", 1, params, c, batch_size=8, epochs=1, seed=1)
        want = tk.local_train(params, c, batch_size=8, epochs=1, seed=1)
        assert _max_err(want, got.tree) < 5e-4


def test_cohort_empty_shard_is_a_noop(task):
    empty = ClientDataset(np.zeros((0, 32, 32, 3), np.float32),
                          np.zeros((0,), np.int64))
    params = task.init_params(0)
    eng = BatchedEngine(task)
    got = eng.result("0", 1, params, empty, batch_size=20, epochs=1, seed=0)
    assert _max_err(params, got.tree) == 0.0


def test_cohort_result_falls_back_on_unknown_params(task, small_clients):
    """A result() whose θ was never submitted (e.g. racing aggregators)
    still trains correctly via the fallback path."""
    params = task.init_params(0)
    other = jax.tree.map(lambda l: l + 0.01, params)
    engine = BatchedEngine(task)
    engine.submit("0", 1, params, small_clients[0], batch_size=20,
                  epochs=1, seed=2)
    got = engine.result("0", 1, other, small_clients[0], batch_size=20,
                        epochs=1, seed=2)
    want = task.local_train(other, small_clients[0], batch_size=20,
                            epochs=1, seed=2)
    assert _max_err(want, FlatModel.pack(got, task.flat_spec).tree) < 5e-4


def test_stale_round_jobs_are_pruned(task, small_clients):
    engine = BatchedEngine(task)
    params = task.init_params(0)
    engine.submit("0", 1, params, small_clients[0], batch_size=20,
                  epochs=1, seed=1)
    engine.submit("0", 3, params, small_clients[0], batch_size=20,
                  epochs=1, seed=3)
    assert [j.tag for j in engine._queue] == [3]


def test_evaluate_many_matches_evaluate(task):
    rng = np.random.default_rng(3)
    test = ClientDataset(rng.normal(size=(100, 32, 32, 3)).astype(np.float32),
                         rng.integers(0, 10, 100))
    models = [task.init_params(s) for s in range(3)]
    many = task.evaluate_many(models, test)
    for p, m in zip(models, many):
        one = task.evaluate(p, test)
        for k in one:
            assert abs(one[k] - m[k]) < 2e-3, (k, one[k], m[k])


# ---------------------------------------------------------------- sessions


def test_make_engine_auto_selection(task):
    from repro.core.tasks import AbstractTask
    assert isinstance(make_engine(None, task), BatchedEngine)
    assert isinstance(make_engine(None, AbstractTask(1000)), SequentialEngine)
    assert isinstance(make_engine("sequential", task), SequentialEngine)
    assert isinstance(make_engine("batched", AbstractTask(1000)),
                      SequentialEngine)      # no cohort surface -> fallback
    with pytest.raises(ValueError):
        make_engine("warp", task)


def test_session_engines_agree():
    """Batched and sequential sessions: identical event trajectory (rounds,
    bytes) and matching model quality."""
    from repro.data import make_classification_task
    from repro.sim.runner import ModestSession

    n = 6
    data = make_classification_task(n, samples_per_node=30, iid=False,
                                    alpha=0.5, seed=0)
    task = cnn_task()
    mcfg = ModestConfig(n_nodes=n, sample_size=3, n_aggregators=2,
                        success_fraction=1.0, ping_timeout=1.0)
    results = {}
    for engine in ("batched", "sequential"):
        results[engine] = ModestSession(
            n_nodes=n, mcfg=mcfg, tcfg=TrainConfig(batch_size=20),
            task=task, data=data, seed=0, eval_every_rounds=5,
            engine=engine).run(25.0)
    rb, rs = results["batched"], results["sequential"]
    assert rb.rounds_completed == rs.rounds_completed
    assert rb.usage["total_bytes"] == rs.usage["total_bytes"]
    ab = {h["round"]: h["accuracy"] for h in rb.history if "accuracy" in h}
    as_ = {h["round"]: h["accuracy"] for h in rs.history if "accuracy" in h}
    assert ab.keys() == as_.keys() and ab
    for k in ab:
        assert abs(ab[k] - as_[k]) < 0.02, (k, ab[k], as_[k])


def test_session_engines_agree_under_fault_schedule():
    """Engine parity must survive fault injection: with an active
    schedule (loss + duplication + jitter + a straggler window) the
    batched and sequential engines still produce byte-identical event
    trajectories and identical injection decisions — fault draws depend
    only on simulator event order, which is engine-independent."""
    from repro.data import make_classification_task
    from repro.sim.fault import (Drop, Duplicate, FaultSchedule, Jitter,
                                 Straggler)
    from repro.sim.runner import ModestSession

    n = 6
    data = make_classification_task(n, samples_per_node=30, iid=False,
                                    alpha=0.5, seed=0)
    task = cnn_task()
    mcfg = ModestConfig(n_nodes=n, sample_size=3, n_aggregators=2,
                        success_fraction=1.0, ping_timeout=1.0)
    sched = FaultSchedule(rules=(Drop(p=0.08), Duplicate(p=0.1, gap=0.2),
                                 Jitter(max_delay=0.15),
                                 Straggler(nodes=("2",), factor=3.0,
                                           t0=5.0, t1=15.0)), seed=13)
    results = {}
    for engine in ("batched", "sequential"):
        results[engine] = ModestSession(
            n_nodes=n, mcfg=mcfg, tcfg=TrainConfig(batch_size=20),
            task=task, data=data, seed=0, eval_every_rounds=5,
            engine=engine, fault=sched).run(25.0)
    rb, rs = results["batched"], results["sequential"]
    assert rb.fault_stats and rb.fault_stats == rs.fault_stats
    assert rb.rounds_completed == rs.rounds_completed
    assert rb.usage == rs.usage                  # byte-identical, per type
    assert [(t, k) for t, k in rb.round_times] == \
        [(t, k) for t, k in rs.round_times]
    ab = {h["round"]: h["accuracy"] for h in rb.history if "accuracy" in h}
    as_ = {h["round"]: h["accuracy"] for h in rs.history if "accuracy" in h}
    assert ab.keys() == as_.keys()
    for k in ab:
        assert abs(ab[k] - as_[k]) < 0.02, (k, ab[k], as_[k])
