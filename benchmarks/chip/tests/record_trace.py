#!/usr/bin/env python3
"""Record a small chip trace for ``test_chip_trace.py`` to read: the
tests' tiny paper-CNN Plexus cell (real widths, six nodes, three local
steps), warmed, then one session under the profiler inside the
benchmark's ``bench.window`` span, as a ``--trace 1`` run takes it.
Run it on a TPU host.

    python3 benchmarks/chip/tests/record_trace.py OUT.json.gz

The trace is written slimmed, as gzipped JSON: the planes
:func:`trace_reduce.reduce_planes` reads (the device's ``XLA Ops`` and
``XLA Modules`` lines, and the benchmark's own host spans), each event as
``[name, start_ns, duration_ns, hlo_module]`` with operation names cut at
160 characters, and beside them the traced session's counts
(``rounds``, ``agg_sizes``, ``n_params``).
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import tempfile

import tiny

import harness  # noqa: E402  (tiny puts the harness on the path)
import trace_reduce  # noqa: E402
from run_cell import find_chips  # noqa: E402

DEVICE_LINES = ("XLA Ops", "XLA Modules")


def _event(ev, device: bool) -> list:
    if not device:
        return [ev.name, ev.start_ns, ev.duration_ns, ""]
    stats = trace_reduce._stats(ev)
    return [ev.name[:160], ev.start_ns, ev.duration_ns,
            str(stats.get("hlo_module", ""))]


def slim(planes) -> list:
    """The planes and events the reduction reads, as plain lists."""
    out = []
    for plane in planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            events = [_event(ev, device) for ev in line.events
                      if device or
                      ev.name.startswith(trace_reduce.SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        out.append({"name": plane.name, "lines": lines})
    return out


def main() -> int:
    out = sys.argv[1]
    find_chips(1)
    config, traffic = tiny.tiny("cnn-modest-diurnal")
    cell = harness.build_cell("cnn-modest-diurnal", config, traffic, 3)
    harness.warm_shapes(cell)
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    try:
        win = harness.run_window(cell, 0.0, harness.Recorder(3),
                                 harness.CompileCounter(), trace_dir=tmp)
        from jax.profiler import ProfileData

        planes = ProfileData.from_file(harness.find_xplane(tmp)).planes
        doc = {"planes": slim(planes), "rounds": win.traced.rounds,
               "agg_sizes": win.traced.agg_sizes,
               "n_params": cell.task.flat_spec.n}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    with gzip.open(out, "wt") as f:
        json.dump(doc, f)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
