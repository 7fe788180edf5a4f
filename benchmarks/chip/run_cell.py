#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run_cell.py --workload cnn-modest-diurnal \\
        --seed 7 --seconds 20 --trace 0

Set-up (timed as ``setup_s``): find the TPU (there is no CPU fallback),
turn on the persistent compile cache, build the task, the data and the
population from ``--seed``, and warm every shape the cell's traffic uses.
The window then runs the traffic's pool of sessions through the public
session entry, in an order drawn from ``--seed``, pass after pass until
``--seconds`` have passed at the end of a pass. ``--trace 1`` runs the
second session under the profiler and reports the per-layer metrics;
``--trace 0`` reports the end-to-end ones. After the window the sampled
answers of the timed path are compared with the plain reference.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
ending with ``checks``, each compared number beside its limit. Everything
else goes to stderr, whose last lines are the same checks.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402

import numpy as np  # noqa: E402

import harness  # noqa: E402


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


@dataclass
class Run:
    """What a per-layer reader is given."""
    window: harness.Window
    flops_per_sample: float
    peaks: dict
    n_params: int


def find_chips(n: int):
    """The accelerator devices, or exit non-zero without a result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"run_cell: no TPU found (JAX platform is "
                 f"{devices[0].platform!r}); there is no CPU fallback")
    if len(devices) < n:
        sys.exit(f"run_cell: the cell needs {n} chips, JAX finds "
                 f"{len(devices)}")
    return devices[:n]


def end_to_end(win: harness.Window, setup_s: float) -> dict:
    return {
        "wall_s_per_round": {"value": win.seconds / win.rounds, "unit": "s"},
        "round_p90_s": {"value": float(np.percentile(win.round_gaps(), 90)),
                        "unit": "s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    m = harness.manifest()
    w = harness.workload(m, args.workload)
    devices = find_chips(w["chips"])
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    import counting

    log(f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)}")
    log(f"compile cache: {enable_compile_cache()}")
    compiles = harness.CompileCounter()
    peaks = counting.peaks(devices[0].device_kind)
    config = harness.load_json("configs", w["config"])
    traffic = harness.load_json("traffic", w["traffic"])
    cell = harness.build_cell(w["name"], config, traffic, args.seed,
                              devices=devices)
    t = time.perf_counter()
    calls = harness.warm_shapes(cell, log=log)
    log(f"warm: {calls} engine calls in {time.perf_counter() - t:.3f} s")
    warm = harness.run_session(cell, -1, harness.Recorder(args.seed),
                               traffic["warmup_seconds"])
    log(f"warm session: {warm.rounds} rounds in {warm.wall_s:.3f} s")
    setup_s = time.perf_counter() - T0
    log(f"setup_s={setup_s} compiles={compiles.count} "
        f"({compiles.seconds:.3f} s)")

    recorder = harness.Recorder(args.seed, harness.sample_counts(config))
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    try:
        win = harness.run_window(cell, args.seconds, recorder, compiles,
                                 trace_dir=trace_dir, log=log)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"window: {win.seconds:.3f} s, {win.attempted} sessions, "
        f"{win.rounds} rounds, {win.compiles} compiles, "
        f"{win.failed} failed; {recorder.copy_s:.3f} s copying samples "
        f"to the host")
    if args.trace:
        log(f"step programs' text for the op scopes: {win.texts_s:.3f} s")
    if win.compiled:
        log(f"compiled in the window: {sorted(set(win.compiled))}")
    for s in win.sessions:
        log(f"session: {s.rounds} rounds, {s.wall_s:.3f} s, {s.jobs} jobs "
            f"in {s.flushes} flushes, {len(s.agg_sizes)} aggregations, "
            f"{s.evals} evaluated, {s.events} events, host s in "
            f"{ {k: round(v, 3) for k, v in s.host_s.items()} }")
    if win.rounds:
        q = np.percentile(win.round_gaps(), [50, 80, 90, 95, 100])
        log(f"round gaps p50/p80/p90/p95/max: {np.round(q, 4).tolist()}")
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in devices)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}

    line = {"correct": False, "attempted": win.attempted,
            "failed": win.failed}
    if args.trace:
        run = Run(win, counting.train_flops_per_sample(config["model"]),
                  peaks, cell.task.flat_spec.n)
        units = {x["name"]: x["unit"] for x in m["per_layer"]}
        metrics = {}
        for name, read in harness.readers(m, w["name"]).items():
            value = read(run)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
        t = win.trace
        if t is not None:
            device["busy_s"] = t.busy_ns / 1e9
            device["window_s"] = t.window_ns / 1e9
            # gaps labelled by the innermost span, the program's or ours
            gaps = (win.program or t).gaps
            line["breakdown"] = {
                "device_ops": t.top_ops(10),
                "idle_gaps": [[n, s / 1e9] for n, s in gaps]}
    else:
        metrics = end_to_end(win, setup_s) if win.rounds else {}
    line["metrics"] = metrics
    line["device"] = device

    t = time.perf_counter()
    checks = harness.check(cell, recorder)
    log(f"reference check: {time.perf_counter() - t:.3f} s")
    line["correct"] = bool(metrics) and harness.correct(win, checks)
    line["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
