"""Names of the program's trace spans and device scopes, in one place.

Host spans are :class:`jax.profiler.TraceAnnotation` s: they record only
while the JAX profiler runs (``jax.profiler.trace``), on the same clock as
the device's operations, so a gap on the device can be put down to the
span the host was in. Keyword arguments of a span become stats of its
event. Device scopes are :func:`jax.named_scope` s: they name the ops of a
compiled program in its ``op_name`` metadata and change no arithmetic.
docs/ENGINE.md ("Tracing a session") shows how to capture a session.

Counters of :class:`~repro.engine.cohort.BatchedEngine`, beside
``flushes`` and ``jobs_run``: ``jobs_served`` (``result()`` calls answered
from a flush), ``batch_bytes_h2d`` (bytes copied to the device for
training inputs: each group's indices, masks and active flags, and the
shards uploaded) and ``shard_uploads`` (client shards the engine copied
to the device; the rest of its jobs ran from resident shards).
"""

# host spans
SIM_EVENT = "repro.sim.event"                # Simulator.run: one event's handler
ENGINE_RESULT = "repro.engine.result"        # BatchedEngine.result
ENGINE_ASSEMBLE = "repro.engine.assemble"    # a flush's index build (jobs=)
ENGINE_DISPATCH = "repro.engine.dispatch"    # a group's copies and dispatches (steps=)
ENGINE_AGGREGATE = "repro.engine.aggregate"  # BatchedEngine.aggregate[_masked] (models=)
ENGINE_EVALUATE = "repro.engine.evaluate"    # BatchedEngine.evaluate_models (models=)

HOST_SPANS = (SIM_EVENT, ENGINE_RESULT, ENGINE_ASSEMBLE, ENGINE_DISPATCH,
              ENGINE_AGGREGATE, ENGINE_EVALUATE)

# device scopes of the cohort train step (and of the scan that reuses it)
STEP_UNPACK = "unpack"          # (S, N) buffer -> per-leaf views
STEP_GRAD = "grad"              # the vmapped per-member gradient
STEP_PACK = "pack"              # per-leaf gradients -> (S, N) buffer
STEP_OPTIMIZER = "optimizer"    # optimizer update and the active-row gating

STEP_SCOPES = (STEP_UNPACK, STEP_GRAD, STEP_PACK, STEP_OPTIMIZER)
