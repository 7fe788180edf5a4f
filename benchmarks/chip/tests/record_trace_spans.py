#!/usr/bin/env python3
"""Record a small chip trace with the program's own spans and scopes for
``test_chip_program_trace.py`` to read: the tests' tiny paper-CNN Plexus
cell, warmed, then one session under the profiler inside the benchmark's
``bench.window`` span, as ``record_trace.py`` takes it. Run it on a TPU
host.

    python3 benchmarks/chip/tests/record_trace_spans.py OUT.json.gz

The trace is written slimmed, as gzipped JSON: the device's ``XLA Ops``
and ``XLA Modules`` lines, each event as ``[name, start_ns, duration_ns,
hlo_module, scope]`` with operation names cut at 160 characters and the
op's step scope resolved while recording (:mod:`program_trace`); the host
spans of both prefixes as ``[name, start_ns, duration_ns, stats]``; and
beside them the traced session's counters.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

import tiny

import program_trace  # noqa: E402  (tiny puts the harness on the path)
import trace_reduce  # noqa: E402
from run_cell import find_chips  # noqa: E402
from trace_session import traced_session  # noqa: E402

DEVICE_LINES = ("XLA Ops", "XLA Modules")


def slim(planes, scopes) -> list:
    """The planes and events the two reductions read, as plain lists."""
    out = []
    for plane in planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            if device:
                ops = line.name == "XLA Ops"
                events = [[ev.name[:160], ev.start_ns, ev.duration_ns,
                           str(trace_reduce._stats(ev).get("hlo_module",
                                                           "")),
                           program_trace._event_scope(ev, scopes).step
                           if ops else ""]
                          for ev in line.events]
            else:
                events = [[ev.name, ev.start_ns, ev.duration_ns,
                           {k: v for k, v in trace_reduce._stats(ev).items()
                            if isinstance(v, (int, float, str))}]
                          for ev in line.events
                          if ev.name.startswith(program_trace.PREFIXES)]
            if events:
                lines.append({"name": line.name, "events": events})
        out.append({"name": plane.name, "lines": lines})
    return out


def main() -> int:
    out = sys.argv[1]
    find_chips(1)
    import harness

    config, traffic = tiny.tiny("cnn-modest-diurnal")
    cell = harness.build_cell("cnn-modest-diurnal", config, traffic, 3)
    harness.warm_shapes(cell)
    stats, recorder, data, _ = traced_session(cell, 0, 3)
    scopes = program_trace.hlo_op_scopes(recorder.program_texts())
    doc = {"planes": slim(data.planes, scopes),
           "counters": vars(program_trace.session_counters(stats)),
           "agg_sizes": stats.agg_sizes, "n_params": cell.task.flat_spec.n}
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with gzip.open(out, "wt") as f:
        json.dump(doc, f)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
