"""Run a tiny four-chip cell on forced host devices, as ``run_cell.py``
runs a cell after finding its chips, and write what it saw as JSON.

    python3 benchmarks/chip/tests/mesh_child.py 4 OUT.json [WORKLOAD]

JAX fixes the device count when it starts, so this runs in a process of
its own with ``xla_force_host_platform_device_count`` set before JAX is
imported. The cell is ``WORKLOAD`` of the benchmark this file lies in
(the tests' tiny Plexus cell by default), cut by ``tiny.tiny``, on all the
process's devices (the program's ``"sharded"`` engine); beside it an
engine the harness builds over the first two devices alone. Its per-layer
readers read the window, whose second session is traced.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile


def main() -> int:
    n, out = int(sys.argv[1]), sys.argv[2]
    name = sys.argv[3] if len(sys.argv) > 3 else "cnn-modest-diurnal"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)

    import jax

    import tiny
    import harness

    devices = jax.devices()
    config, traffic = tiny.tiny(name)
    # every evaluation sweep a session can make, as the real cells warm
    traffic["warm"]["max_eval"] = tiny.TINY_NODES
    cell = harness.build_cell(name, config, traffic, 5, devices=devices)
    engines = []
    make = cell.new_engine

    def new_engine():
        engines.append(make())
        return engines[-1]

    cell.new_engine = new_engine
    compiles = harness.CompileCounter()
    harness.warm_shapes(cell)
    harness.run_session(cell, -1, harness.Recorder(cell.seed),
                        traffic["warmup_seconds"])
    recorder = harness.Recorder(cell.seed,
                                harness.sample_counts(cell.config))
    with tempfile.TemporaryDirectory() as trace_dir:
        win = harness.run_window(cell, 1.0, recorder, compiles,
                                 trace_dir=trace_dir)
    checks = harness.check(cell, recorder)
    import counting
    from run_cell import Run

    run = Run(win, counting.train_flops_per_sample(config["model"]),
              counting.peaks("TPU v5 lite"), cell.task.flat_spec.n)
    readings = {k: read(run) for k, read in
                harness.readers(harness.manifest(), name).items()}
    sub = harness.engine_factory(cell.task, devices[:2])()

    def mesh_ids(engine):
        mesh = getattr(engine, "mesh", None)
        return None if mesh is None else [d.id for d in mesh.devices.flat]

    doc = {"devices": [d.id for d in devices],
           "engines": [type(e).__name__ for e in engines],
           "meshes": [mesh_ids(e) for e in engines],
           "subset": [type(sub).__name__, mesh_ids(sub)],
           "window_compiles": win.compiles, "compiled": win.compiled,
           "sessions": win.attempted, "rounds": win.rounds,
           "failed": win.failed, "checks": checks, "readings": readings,
           "counters": win.traced.counters,
           "correct": harness.correct(win, checks)}
    with open(out, "w") as f:
        json.dump(doc, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
