#!/usr/bin/env python3
"""Trace sessions of a cell on the chip and read the program's own spans,
scopes and counters (:mod:`program_trace`), beside what
:mod:`trace_reduce` reads, and what tracing costs.

    python3 benchmarks/chip/trace_session.py --workload cnn-modest-diurnal \\
        --seed 7 --out trace_session.json

Set-up as ``run_cell.py`` does it (the cell's inputs from the seed, every
shape warmed, one warm-up session). Then the first session of the seed's
pass order runs four times: untraced, traced, untraced, traced, each traced
one inside a ``bench.window`` span with the profiler options of a
``--trace 1`` run. A pool session does the same work every time it runs,
so the four wall times show what the profiler costs while it records and
what it leaves behind. Each traced session is reduced by both modules; the
op scopes come from the compiled text of the step programs the session
ran. Prints one JSON line (and writes it to ``--out``). On a program without the spans the program's numbers read
``None`` and the costs are still measured.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import harness  # noqa: E402
import program_trace  # noqa: E402
import trace_reduce  # noqa: E402

SPAN_NAMES = ("bench.result", "bench.aggregate", "bench.evaluate",
              "repro.sim.event", "repro.engine.result",
              "repro.engine.assemble", "repro.engine.dispatch",
              "repro.engine.aggregate", "repro.engine.evaluate")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def span_cost_us(n: int = 200_000) -> dict:
    """Host microseconds per span with the profiler off, without and with
    one metadata keyword, and of the bare loop."""
    from jax.profiler import TraceAnnotation

    out = {}
    for label, kw in (("span", {}), ("span_with_metadata", {"jobs": 10})):
        t = time.perf_counter()
        for _ in range(n):
            with TraceAnnotation("repro.engine.assemble", **kw):
                pass
        out[label] = (time.perf_counter() - t) / n * 1e6
    t = time.perf_counter()
    for _ in range(n):
        pass
    out["loop"] = (time.perf_counter() - t) / n * 1e6
    return out


def traced_session(cell, index: int, seed: int, keep: str = None):
    """Run pool session ``index`` under the profiler as a ``--trace 1`` run
    does; returns (session stats, recorder, the trace's ``ProfileData``,
    seconds: session, writing and reading the trace). ``keep`` copies the
    ``.xplane.pb``."""
    import jax
    from jax.profiler import ProfileData, ProfileOptions, TraceAnnotation

    options = ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    recorder = harness.Recorder(seed)
    recorder.sync = recorder.note_programs = True
    tmp = tempfile.mkdtemp(prefix="trace_session_")
    try:
        t0 = time.perf_counter()
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            with TraceAnnotation("bench.window"):
                stats = harness.run_session(cell, index, recorder)
        finally:
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
        t2 = time.perf_counter()
        path = harness.find_xplane(tmp)
        if keep:
            shutil.copy(path, keep)
        data = ProfileData.from_file(path)
        t3 = time.perf_counter()
        seconds = {"session": t1 - t0, "write": t2 - t1, "read": t3 - t2,
                   "xplane_bytes": os.path.getsize(path)}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return stats, recorder, data, seconds


def reading(stats, recorder, data, seconds) -> dict:
    """What one traced session reads: the program's seven numbers and the
    benchmark's reduction, span totals, gaps and costs."""
    t = time.perf_counter()
    # ``data.planes`` can be iterated once: each reduction asks anew
    base = trace_reduce.reduce_planes(data.planes)
    texts = recorder.program_texts(log)
    scopes = program_trace.hlo_op_scopes(texts)
    prog = program_trace.reduce_program(data.planes, scopes)
    seconds["reduce"] = time.perf_counter() - t
    c = program_trace.session_counters(stats)
    out = {"seconds": seconds, "counters": vars(c),
           "session": {"rounds": stats.rounds, "events": stats.events,
                       "wall_s": stats.wall_s},
           "compiled_programs": len(texts)}
    if base is not None:
        out["bench"] = {
            "idle_share": 100.0 * base.idle_share,
            "host_loop_share": 100.0 * (1.0 - base.span_ns(
                "bench.result", "bench.aggregate", "bench.evaluate")
                / base.window_ns),
            "window_s": base.window_ns / 1e9,
            "train_dev_ms_per_round": base.programs(
                r"^jit_(step|train_scan)$") / base.devices / 1e6
            / max(stats.rounds, 1),
            "gaps": [[n, s / 1e9] for n, s in base.gaps]}
    if prog is not None:
        out["numbers"] = program_trace.numbers(prog, c)
        out["scope_coverage"] = program_trace.scope_coverage(prog)
        out["scope_ms"] = {k or "(none)": v / 1e6 / prog.devices
                           for k, v in prog.scope_ns.items()}
        out["span_ms"] = {n: prog.total_ns(n) / 1e6 for n in SPAN_NAMES}
        out["span_count"] = {n: prog.count(n) for n in SPAN_NAMES}
        out["idle_ms"] = prog.idle_ns / 1e6
        out["gaps"] = [[n, s / 1e9] for n, s in prog.gaps]
    return out


def run(cell, seed: int, keep_dir: str = None) -> dict:
    """The four sessions after the set-up (see the module's docstring)."""
    index = harness.pass_order(seed, cell.traffic["pass_sessions"])[0]
    out = {"index": index, "walls_s": [], "traced": []}
    for k, traced in enumerate((False, True, False, True)):
        if not traced:
            stats = harness.run_session(cell, index, harness.Recorder(seed))
            out["walls_s"].append(stats.wall_s)
            log(f"untraced session: {stats.wall_s:.3f} s")
            continue
        keep = os.path.join(keep_dir, f"session{k}.xplane.pb") \
            if keep_dir else None
        got = traced_session(cell, index, seed, keep)
        out["walls_s"].append(got[0].wall_s)
        r = reading(*got)
        log(f"traced session: {json.dumps(r['seconds'])}")
        out["traced"].append(r)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out")
    ap.add_argument("--keep-dir", help="copy each trace's .xplane.pb here")
    args = ap.parse_args()

    from run_cell import find_chips

    m = harness.manifest()
    w = harness.workload(m, args.workload)
    devices = find_chips(w["chips"])
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    config = harness.load_json("configs", w["config"])
    traffic = harness.load_json("traffic", w["traffic"])
    cell = harness.build_cell(w["name"], config, traffic, args.seed,
                              devices=devices)
    harness.warm_shapes(cell, log=log)
    harness.run_session(cell, -1, harness.Recorder(args.seed),
                        traffic["warmup_seconds"])
    setup_s = time.perf_counter() - T0
    log(f"setup_s={setup_s:.3f}")
    line = {"workload": w["name"], "seed": args.seed, "setup_s": setup_s,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind},
            "span_cost_us": span_cost_us()}
    line.update(run(cell, args.seed, args.keep_dir))
    text = json.dumps(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
