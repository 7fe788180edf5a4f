#!/usr/bin/env python3
"""Record a small chip trace of a step whose ``grad`` scope holds two
named inner scopes, for ``test_chip_new_family.py`` to read the model
scopes from. Run it on a TPU host.

    python3 benchmarks/chip/tests/record_scope_trace.py OUT.json.gz

The step is a jitted ``step`` (so its program is ``jit_step``, as the
cohort step's is) that vmaps the gradient of a two-part model over four
members under ``grad``, the parts under the scopes ``mixer`` and
``experts``, and applies it under ``optimizer``. It is warmed, then run
twenty times under the profiler inside a ``bench.window`` span. The trace
is written slimmed, as gzipped JSON: the device's ``XLA Ops`` and ``XLA
Modules`` lines, each event as ``[name, start_ns, duration_ns,
hlo_module]`` with the name cut at 160 characters and no scope resolved,
the window span, and beside them the step's compiled text, from which
:func:`program_trace.hlo_op_scopes` reads the scopes as on any TPU trace.
"""

from __future__ import annotations

import gzip
import json
import os
import sys
import tempfile

import tiny  # noqa: F401  (puts the harness on the path)

import harness  # noqa: E402
import trace_reduce  # noqa: E402
from run_cell import find_chips  # noqa: E402

SCOPES = ("mixer", "experts")
DEVICE_LINES = ("XLA Ops", "XLA Modules")


def model_loss(p, x):
    import jax
    import jax.numpy as jnp

    with jax.named_scope("mixer"):
        h = jnp.tanh(x @ p["mix"])
    with jax.named_scope("experts"):
        h = jax.nn.relu(h @ p["up"]) @ p["down"]
    return jnp.mean(h * h)


def step(p, x):
    import jax

    with jax.named_scope("grad"):
        g = jax.vmap(jax.grad(model_loss))(p, x)
    with jax.named_scope("optimizer"):
        return jax.tree.map(lambda a, b: a - 0.01 * b, p, g)


def inputs(members: int = 4, rows: int = 256, d: int = 512, f: int = 2048):
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.key(0), 4)
    p = {"mix": jax.random.normal(k[0], (members, d, d)) * d ** -0.5,
         "up": jax.random.normal(k[1], (members, d, f)) * d ** -0.5,
         "down": jax.random.normal(k[2], (members, f, d)) * f ** -0.5}
    return p, jax.random.normal(k[3], (members, rows, d), jnp.float32)


def slim(planes) -> list:
    out = []
    for plane in planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            events = [[ev.name[:160], ev.start_ns, ev.duration_ns,
                       str(trace_reduce._stats(ev).get("hlo_module", ""))]
                      for ev in line.events
                      if device or ev.name == trace_reduce.WINDOW_SPAN]
            if events:
                lines.append({"name": line.name, "events": events})
        out.append({"name": plane.name, "lines": lines})
    return out


def main() -> int:
    out = sys.argv[1]
    find_chips(1)
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    fn = jax.jit(step)
    p, x = inputs()
    text = fn.lower(p, x).compile().as_text()
    p = jax.block_until_ready(fn(p, x))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for _ in range(20):
                p = fn(p, x)
            jax.block_until_ready(p)
        jax.profiler.stop_trace()
        data = ProfileData.from_file(harness.find_xplane(tmp))
        planes = slim(data.planes)
    doc = {"planes": planes, "texts": [text], "scopes": list(SCOPES)}
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with gzip.open(out, "wt") as f:
        json.dump(doc, f)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
