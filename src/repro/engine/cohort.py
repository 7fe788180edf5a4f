"""Vmapped cohort training: collapse S·B per-node dispatches to B.

The simulator samples a cohort S^k every round and each sampled node
trains the *same* aggregated model on its own shard. Training is a pure
function of ``(θ, shard, seed)``, so the engine can run the whole cohort
as one ``(S, N)`` flat-buffer batch without changing event semantics —
the simulator still attributes per-node train *durations* from the cost
model; only the wall-clock cost of computing the results changes.

Flow: nodes ``submit()`` when a round's training starts (message arrival)
and ``result()`` when the simulated duration elapses. The first demanded
result flushes everything queued at that sim-time as one vmapped batch —
cohort members whose messages arrived earlier ride along, so a round
typically costs one flush. Jobs whose round was cancelled mid-flight are
pruned on the node's next submit; a ``result()`` whose job was never
queued (or whose θ doesn't match the queued one, e.g. a second aggregator
won the race with a different partial average) falls back to the
sequential path — correctness never depends on the cache.

Batching semantics (the ragged-tail fix, shared with the sequential
path): client batches are padded to a uniform shape with a per-row loss
mask — masked rows contribute exactly zero gradient, unlike the old
sample replication which silently upweighted repeated samples. Cohort
members are grouped by step count before vmapping (non-IID shard sizes
are ragged), so no member rides through wasted no-op steps; the step
itself additionally gates params and optimizer state with a per-row
``active`` mask, keeping any padded grouping policy (e.g. full-width
batches on TPU) exact by construction.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.flat import FlatModel, as_buffer, as_tree
from repro.engine.lowering import masked_loss_for
from repro.engine.optim_flat import build_flat


class SequentialEngine:
    """Reference engine: the exact pre-engine compute path — per-node
    ``task.local_train``, per-leaf aggregation, per-model evaluation."""

    name = "sequential"

    def __init__(self, task):
        self.task = task

    def submit(self, node_id, tag, params, client, *, batch_size, epochs,
               seed) -> None:
        pass

    def plan_cohort(self, tag, node_ids, params, *, batch_size, epochs,
                    seed) -> None:
        pass

    def register_client(self, node_id, client) -> None:
        pass

    def result(self, node_id, tag, params, client, *, batch_size, epochs,
               seed, lr_scale: float = 1.0):
        return self.task.local_train(params, client, batch_size=batch_size,
                                     epochs=epochs, seed=seed,
                                     lr_scale=lr_scale)

    def aggregate(self, models, weights=None):
        return self.task.aggregate_sequential(models, weights)

    def aggregate_masked(self, models, seeds, signs, weights=None):
        """Secure-agg path (repro.secureagg): unmask+aggregate sealed
        FlatModels in one fused pass. The sequential engine delegates to
        the task like :meth:`aggregate` does."""
        return self.task.aggregate_masked(models, seeds, signs, weights)

    def evaluate_models(self, models, test):
        return [self.task.evaluate(p, test) for p in models]


@dataclass
class _Job:
    node_id: str
    tag: int
    params: Any                 # pinned reference: identity keys the cache
    client: Any
    batch_size: int
    epochs: int
    seed: int
    confirmed: bool = True      # False for plan-ahead jobs (send-time hook)

    @property
    def key(self) -> Tuple[str, int, int]:
        return (self.node_id, self.tag, id(self.params))

    @property
    def hp(self) -> Tuple[int, int, int]:
        """Training hyperparameters — a cached result is only valid for
        a demand with the same (batch_size, epochs, seed)."""
        return (self.batch_size, self.epochs, self.seed)


class BatchedEngine:
    """Flat-model vmapped cohort trainer for a :class:`JaxTask`."""

    name = "batched"

    def __init__(self, task):
        self.task = task
        self.spec = task.flat_spec
        self._queue: List[_Job] = []
        # key -> (result FlatModel, the θ the job trained from, confirmed,
        #         the job's (batch_size, epochs, seed))
        self._done: Dict[Tuple[str, int, int],
                         Tuple[FlatModel, Any, bool, tuple]] = {}
        self._alt_specs: Dict[tuple, Any] = {}
        self._clients: Dict[str, Any] = {}
        self._served: set = set()   # (node, tag) already delivered
        # The jitted step is cached on the task: new engines (one per
        # session) must not retrace — compilation is paid once per task.
        self._opt, self._step, self._scan = _cohort_ops(task)
        self.flushes = 0            # introspection for tests/benchmarks
        self.jobs_run = 0

    # ------------------------------------------------------------------ api

    def register_client(self, node_id, client) -> None:
        """Teach the engine a node's shard so ``plan_cohort`` can build
        that node's batches (sessions call this for every node)."""
        self._clients[node_id] = client

    def plan_cohort(self, tag, node_ids, params, *, batch_size, epochs,
                    seed) -> None:
        """Send-time hook: the aggregator of round ``tag`` knows the whole
        sampled cohort and the (immutable, already-in-flight) θ̄, so the
        cohort's trainings can be queued before the TrainMsgs arrive —
        without this, WAN transfer staggering (transfer ≫ train duration)
        fragments cohorts into S=1 flushes. A plan never overrides a
        confirmed (arrival-time) submit, and results are value-checked
        before use, so racing aggregators stay correct.
        """
        if params is None:
            return
        self._gc(tag)
        for nid in node_ids:
            client = self._clients.get(nid)
            if client is None:
                continue
            if (nid, tag) in self._served:
                continue   # a later aggregator re-planning a done round
            if any(j.node_id == nid and j.tag == tag for j in self._queue) \
                    or any(k[0] == nid and k[1] == tag for k in self._done):
                continue                      # first plan/submit wins
            self._prune(nid, tag)
            self._queue.append(_Job(nid, tag, params, client, batch_size,
                                    epochs, seed, confirmed=False))

    def submit(self, node_id, tag, params, client, *, batch_size, epochs,
               seed) -> None:
        if params is None or client is None:
            return
        self._gc(tag)
        self._prune(node_id, tag)
        job = _Job(node_id, tag, params, client, batch_size, epochs, seed)
        if job.key in self._done:
            return
        for i, j in enumerate(self._queue):
            if j.node_id == node_id and j.tag == tag:
                if j.params is params and j.hp == job.hp:
                    return                   # already queued (plan or dup)
                if not j.confirmed:
                    self._queue[i] = job     # arrival overrides the plan
                    return
        self._queue.append(job)

    def result(self, node_id, tag, params, client, *, batch_size, epochs,
               seed, lr_scale: float = 1.0):
        hp = (batch_size, epochs, seed)
        hit = self._lookup(node_id, tag, params, hp)
        if hit is None and any(j.node_id == node_id and j.tag == tag
                               for j in self._queue):
            self._flush()
            hit = self._lookup(node_id, tag, params, hp)
        if hit is None:
            # never planned (θ or hyperparameter mismatch, or unknown
            # node): train it alone, same math
            self.submit(node_id, tag, params, client, batch_size=batch_size,
                        epochs=epochs, seed=seed)
            self._flush()
            hit = self._lookup(node_id, tag, params, hp)
        if hit is not None:
            self._served.add((node_id, tag))
            return hit
        self._served.add((node_id, tag))
        return self.task.local_train(params, client, batch_size=batch_size,
                                     epochs=epochs, seed=seed,
                                     lr_scale=lr_scale)

    # -------------------------------------------------------------- internals

    _max_tag = 0

    def _gc(self, tag: int) -> None:
        """Drop *plan-originated* bookkeeping more than a few rounds
        stale: plans for nodes that crashed or lost the round race are
        never demanded. Confirmed submits are exempt — a D-SGD straggler
        may legitimately run many rounds behind the population — and are
        instead pruned per node by ``_prune``."""
        self._max_tag = max(self._max_tag, tag)
        horizon = self._max_tag - 3
        # confirmed entries get a much longer leash (a node that crashed
        # mid-train never demands its result; a permanently-departed one
        # must not pin a buffer forever)
        chorizon = self._max_tag - 50
        if horizon > 0:
            self._queue = [j for j in self._queue
                           if j.tag >= (horizon if not j.confirmed
                                        else chorizon)]
            for key in [k for k, v in list(self._done.items())
                        if k[1] < (horizon if not v[2] else chorizon)]:
                del self._done[key]
            self._served = {s for s in self._served if s[1] >= horizon}

    def _prune(self, node_id, tag) -> None:
        """A node acting at round ``tag`` cancels its stale lower rounds."""
        self._queue = [j for j in self._queue
                       if not (j.node_id == node_id and j.tag < tag)]
        for key in [k for k in self._done
                    if k[0] == node_id and k[1] < tag]:
            del self._done[key]

    def _lookup(self, node_id, tag, params, hp):
        """Cached result for (node, tag) trained from θ == ``params`` with
        the same (batch_size, epochs, seed).

        θ matches by object identity first; value equality as the
        tiebreak — with a > 1 aggregators and sf = 1 both aggregators
        push numerically equal θ̄ as distinct objects, and the planned one
        may not be the object the node ends up training from.
        """
        key = (node_id, tag, id(params))
        entry = self._done.get(key)
        if entry is not None and entry[3] == hp:
            return self._done.pop(key)[0]
        for k in list(self._done):
            if k[0] == node_id and k[1] == tag and self._done[k][3] == hp:
                if self._same_value(self._done[k][1], params):
                    return self._done.pop(k)[0]
        return None

    def _same_value(self, a, b) -> bool:
        """Tight allclose, not bit equality: racing aggregators of the
        same round with sf = 1 average the same models in different
        arrival orders, so their θ̄ differ by fp summation order (~1e-7).
        Using either is within the engine's tolerance contract; genuinely
        different partial averages (sf < 1) are far outside these bounds
        and fall back."""
        if a is b:
            return True
        try:
            ab = as_buffer(a, self.spec)
            bb = as_buffer(b, self.spec)
            return bool(jnp.allclose(ab, bb, rtol=1e-6, atol=1e-6))
        except (ValueError, TypeError):      # layouts or dtypes differ
            return False

    def aggregate(self, models, weights=None):
        """Whole-model one-pass aggregation (stays flat: FlatModel out)."""
        return self.task.aggregate(models, weights)

    def aggregate_masked(self, models, seeds, signs, weights=None):
        """Fused unmask→aggregate over sealed FlatModels (secure agg)."""
        return self.task.aggregate_masked(models, seeds, signs, weights)

    def evaluate_models(self, models, test):
        return self.task.evaluate_many(models, test)

    # ----------------------------------------------------------------- flush

    def _flush(self) -> None:
        jobs, self._queue = self._queue, []
        if not jobs:
            return
        # One vmapped group per (batch_size, epochs, n_steps): batch
        # shapes must agree, and bucketing by step count keeps a short
        # client from riding along through masked no-op steps (non-IID
        # partitions make shard sizes — and so step counts — ragged).
        groups: Dict[Tuple[int, int, int], List[Tuple[_Job, list]]] = {}
        for j in jobs:
            batches = self.task._padded_batches(j.client, j.batch_size,
                                                seed=j.seed, epochs=j.epochs)
            if not batches:                   # empty shard: training is a
                self._done[j.key] = (         # no-op, like the sequential
                    FlatModel(as_buffer(j.params, self.spec),  # path
                              self._out_spec(j.params)),
                    j.params, j.confirmed, j.hp)
                continue
            groups.setdefault((j.batch_size, j.epochs, len(batches)),
                              []).append((j, batches))
        for group in groups.values():
            # Cap the vmap width in the big-compute regime: on the CPU
            # backend the per-model cost of the vmapped step rises past
            # S≈3 (batch-grouped conv lowering), so wide cohorts run as a
            # few medium chunks. Small per-step volumes take the fused
            # scan path instead, which handles full width well. TPUs want
            # the full width everywhere; the cap is backend-tuned.
            x0 = group[0][1][0][0]
            step_elems = len(group) * int(np.prod(x0.shape))
            width = len(group) if step_elems <= _SCAN_VOLUME \
                else _max_vmap_width()
            for lo in range(0, len(group), width):
                self._run_group(group[lo:lo + width])

    def _run_group(self, pairs: List[Tuple[_Job, list]]) -> None:
        jobs = [j for j, _ in pairs]
        self.flushes += 1
        self.jobs_run += len(jobs)
        S = len(jobs)
        per_job = [b for _, b in pairs]
        T = max(len(b) for b in per_job)
        x0, y0 = per_job[0][0][0], per_job[0][0][1]
        xs = np.zeros((T, S) + x0.shape, x0.dtype)
        ys = np.zeros((T, S) + y0.shape, y0.dtype)
        ms = np.zeros((T, S, x0.shape[0]), np.float32)
        act = np.zeros((T, S), np.bool_)
        for s, batches in enumerate(per_job):
            for t, (x, y, m) in enumerate(batches):
                xs[t, s], ys[t, s], ms[t, s], act[t, s] = x, y, m, True

        buf = self._place(jnp.stack([as_buffer(j.params, self.spec)
                                     for j in jobs]))
        state = self._opt.init(buf)
        # Form selection (both are the same step math): small per-step
        # volume → one fused scan dispatch for the whole cohort round;
        # large volume → one dispatch per batch index (XLA-CPU pessimizes
        # big conv bodies inside while-loops, measured ~2× slower).
        if xs[0].size <= _SCAN_VOLUME and T > 1:
            buf = self._scan(buf, state, jnp.asarray(xs), jnp.asarray(ys),
                             jnp.asarray(ms), jnp.asarray(act))
        else:
            for t in range(T):
                buf, state = self._step(buf, state, jnp.asarray(xs[t]),
                                        jnp.asarray(ys[t]),
                                        jnp.asarray(ms[t]),
                                        jnp.asarray(act[t]))
        for s, j in enumerate(jobs):
            self._done[j.key] = (FlatModel(buf[s], self._out_spec(j.params)),
                                 j.params, j.confirmed, j.hp)

    def _place(self, buf):
        """Device-placement hook for the stacked ``(S, N)`` cohort buffer;
        the MeshEngine overrides this to shard N over its mesh."""
        return buf

    def _out_spec(self, params):
        """Results must come back in the *submitted* params' dtypes (e.g. a
        bf16-cast model trained through the fp32 engine stays bf16)."""
        from repro.engine.flat import FlatSpec
        if isinstance(params, FlatModel):
            return params.spec
        leaves = self.spec.treedef.flatten_up_to(params)
        dts = tuple(np.dtype(l.dtype) for l in leaves)
        if dts == self.spec.dtypes:
            return self.spec
        alt = self._alt_specs.get(dts)
        if alt is None:
            alt = FlatSpec(self.spec.treedef, self.spec.shapes, dts)
            self._alt_specs[dts] = alt
        return alt

class MeshEngine(BatchedEngine):
    """BatchedEngine whose flat hot-path buffers are sharded over a
    device mesh (ROADMAP item 2, docs/SHARDING.md).

    The ``(S, N)``/``(P, N)`` buffers shard the parameter axis N over the
    mesh's ``model`` axis (:meth:`FlatSpec.sharding`); the jitted cohort
    step and the flat optimizer run on donated sharded buffers, and
    aggregation takes the per-shard one-pass path. Event semantics are
    untouched — same simulated rounds, durations, and byte accounting as
    ``batched``; only where the arithmetic runs changes. Results are
    fp32-tolerance equal to the single-device engine, and the fused
    aggregate→quantize int8 codes are bit-identical.
    """

    name = "sharded"

    def __init__(self, task, mesh):
        super().__init__(task)
        self.mesh = mesh
        self.shardings = task.flat_spec.sharding(mesh)
        # re-resolve the cohort ops against the sharded layout (the
        # superclass grabbed the single-device set; both are cached on
        # the task, so neither is retraced across sessions)
        self._opt, self._step, self._scan = _cohort_ops(
            task, shardings=self.shardings)

    def _place(self, buf):
        return jax.device_put(buf, self.shardings.stack)

    def aggregate(self, models, weights=None):
        return self.task.aggregate(models, weights,
                                   shardings=self.shardings)

    def aggregate_masked(self, models, seeds, signs, weights=None):
        return self.task.aggregate_masked(models, seeds, signs, weights,
                                          shardings=self.shardings)


# Per-step element-count threshold below which the whole cohort round is
# one fused scan dispatch instead of one dispatch per batch index.
_SCAN_VOLUME = 65536


def _max_vmap_width() -> int:
    """Widest vmapped model batch per dispatch (see _flush). Asked at
    flush time, not import time: importing the engine must not claim a
    device."""
    return 16 if jax.default_backend() == "tpu" else 3


def _cohort_ops(task, shardings=None):
    """(flat optimizer, per-batch step jit, whole-round scan jit) for
    ``task``, cached on it (one entry per flat-buffer sharding).

    The vmapped step collapses S·B per-node dispatches to B (or to 1 in
    scan form), with the ``(S, N)`` params and optimizer-state buffers as
    the donated carry. Per-row ``active`` gates params *and* state, so a
    member with fewer local batches than the group's max would be carried
    through trailing slots untouched — under the current same-step-count
    grouping in ``_flush`` the mask is always all-True, but the gating
    keeps any padded grouping policy exact.

    With ``shardings`` (a :class:`repro.sharding.FlatShardings`) the
    per-row gradients are computed on *replicated* leaves (the model
    math needs whole tensors; letting GSPMD repartition it would change
    fp reduction order and break the engine-equivalence contract), while
    the optimizer state, its update, and the parameter write stay
    sharded over the model axis — all elementwise over N, so sharding
    them cannot change any value. Net effect: results are bit-equal to
    the batched engine, and the N-proportional optimizer buffers (the
    memory that scales with model size) live sharded and donated.
    """
    cache = getattr(task, "_cohort_ops_cache", None)
    if cache is None:
        cache = task._cohort_ops_cache = {}
    if shardings in cache:
        return cache[shardings]
    spec = task.flat_spec
    loss = masked_loss_for(task)
    opt = build_flat(task.tcfg)
    to_batch = task._to_batch
    opt_update = opt.update
    if shardings is None:
        pin = rep = lambda b: b                       # noqa: E731
    else:
        pin = lambda b: jax.lax.with_sharding_constraint(   # noqa: E731
            b, shardings.stack)
        rep = lambda b: jax.lax.with_sharding_constraint(   # noqa: E731
            b, shardings.replicated)

    def step(buf, state, xb, yb, mb, active):
        ptree = spec.unpack_stacked(rep(buf))

        def grad_one(p, x, y, m):
            return jax.grad(loss)(p, to_batch(x, y, m))

        gtree = jax.vmap(grad_one)(ptree, xb, yb, mb)
        g = rep(spec.pack_stacked(gtree))
        upd, nstate = opt_update(g, state, buf)
        keep = active[:, None]
        nbuf = pin(jnp.where(keep, buf + upd, buf))
        nstate = {k: (pin(jnp.where(keep, v, state[k])) if v.ndim == 2
                      else jnp.where(active, v, state[k]))
                  for k, v in nstate.items()}
        return nbuf, nstate

    def train_scan(buf, state, xs, ys, ms, act):
        def body(carry, batch):
            return step(*carry, *batch), None

        (buf, _), _ = jax.lax.scan(body, (buf, state), (xs, ys, ms, act))
        return buf

    # scan returns only the params buffer, so only it is donatable (a
    # donated-but-unreturned state would just warn)
    ops = (opt, jax.jit(step, donate_argnums=(0, 1)),
           jax.jit(train_scan, donate_argnums=(0,)))
    cache[shardings] = ops
    return ops


def make_engine(kind: Optional[str], task):
    """``kind``: "batched" | "sharded" | "sequential" | None (auto).

    Auto picks batched for tasks that expose the flat/cohort surface
    (:class:`~repro.models.tasks.JaxTask`) and sequential otherwise
    (e.g. :class:`~repro.core.tasks.AbstractTask` byte-only runs, where
    there is nothing to compute). "sharded" runs the batched engine with
    its flat buffers sharded over the local device mesh; on a single
    device it falls back to "batched" (sharding would be a no-op).
    """
    if kind is None:
        kind = "batched" if getattr(task, "supports_cohort", False) \
            else "sequential"
    if kind == "sharded":
        if not getattr(task, "supports_cohort", False):
            return SequentialEngine(task)
        from repro.launch.mesh import make_engine_mesh
        mesh = make_engine_mesh()
        if mesh is None:
            return BatchedEngine(task)
        return MeshEngine(task, mesh)
    if kind == "batched":
        if not getattr(task, "supports_cohort", False):
            return SequentialEngine(task)
        return BatchedEngine(task)
    if kind == "sequential":
        return SequentialEngine(task)
    raise ValueError(f"unknown engine {kind!r} "
                     "(expected 'batched', 'sharded' or 'sequential')")


__all__ = ["BatchedEngine", "MeshEngine", "SequentialEngine", "make_engine",
           "FlatModel", "as_tree"]
