"""The reduction from a profiler trace to the per-layer numbers, on planes
built by hand in the layout a TPU trace has (``/device:TPU:<i>`` planes
with ``XLA Ops`` and ``XLA Modules`` lines, benchmark spans on a host
plane), and on a small trace recorded on a TPU v5e by
``record_trace.py`` (``data/cnn-tiny-trace.json.gz``)."""

import gzip
import json
import os
from types import SimpleNamespace as NS

import pytest

import trace_reduce as tr
from run_cell import Run

def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def _plane(name, **lines):
    return NS(name=name, lines=[NS(name=k, events=v)
                                for k, v in lines.items()])


def test_union_clips_and_merges():
    assert tr.union_ns([(0, 10), (5, 15), (20, 30)], 0, 100) == 25
    assert tr.union_ns([(0, 10), (5, 15), (20, 30)], 8, 25) == 12
    assert tr.union_ns([], 0, 10) == 0


def test_reduction_by_hand():
    host = _plane("/host:CPU", python=[
        _ev("bench.window", 100, 900),
        _ev("bench.result", 150, 100),
        _ev("bench.aggregate", 600, 50),
        _ev("PjitFunction(step)", 160, 10),
    ])
    dev = _plane(
        "/device:TPU:0",
        **{"XLA Ops": [_ev("%fusion.1 = f32[4] fusion(...)", 200, 100,
                           hlo_module="jit_step(3)"),
                       _ev("%custom-call.2 = tpu_custom_call", 620, 20,
                           hlo_module="jit__fused(9)"),
                       _ev("%copy.3 = f32[4] copy", 50, 100,
                           hlo_module="jit_pack(1)")],
           "XLA Modules": [_ev("jit_step(3)", 200, 100),
                           _ev("jit__fused(9)", 615, 30)]})
    r = tr.reduce_planes([host, dev])
    assert r.window == (100, 1000) and r.devices == 1
    # busy: [100, 150) of the copy, [200, 300), [620, 640)
    assert r.busy_ns == 50 + 100 + 20
    assert r.idle_share == pytest.approx(1 - 170 / 900)
    assert r.programs(r"^jit_step$") == 100
    assert r.op_ns[("jit__fused", "%custom-call.2 = tpu_custom_call")] == 20
    assert r.span_ns("bench.result", "bench.aggregate") == 150
    assert r.span_durations("bench.aggregate") == [50]
    assert r.top_ops(1) == [["jit_step/%fusion.1", 100e-9]]
    # gaps: [150, 200) in bench.result, [300, 620) host, [640, 1000) host
    assert r.gaps == [("host", 360), ("host", 320), ("bench.result", 50)]


def test_ops_without_a_module_stat_go_to_the_enclosing_program():
    host = _plane("/host:CPU", python=[_ev("bench.window", 0, 1000)])
    dev = _plane(
        "/device:TPU:0",
        **{"XLA Ops": [_ev("%fusion.1 = f32[4] fusion(...)", 110, 50),
                       _ev("%custom-call.2 = tpu_custom_call", 420, 30),
                       _ev("%copy.3 = f32[4] copy", 700, 10)],
           "XLA Modules": [_ev("jit_step(3)", 100, 100),
                           _ev("jit__fused(9)", 400, 100)]})
    r = tr.reduce_planes([host, dev])
    assert r.op_ns[("jit_step", "%fusion.1 = f32[4] fusion(...)")] == 50
    assert r.op_ns[("jit__fused", "%custom-call.2 = tpu_custom_call")] == 30
    assert r.op_ns[("", "%copy.3 = f32[4] copy")] == 10
    assert [k for k, _ in r.top_ops(3)] == [
        "jit_step/%fusion.1", "jit__fused/%custom-call.2", "/%copy.3"]


def test_no_window_or_no_device_gives_nothing():
    host = _plane("/host:CPU", python=[_ev("bench.result", 0, 10)])
    assert tr.reduce_planes([host]) is None


def test_program_names_drop_the_run_id():
    assert tr.program_name("jit_step(12)") == "jit_step"
    assert tr.program_name("jit__fused") == "jit__fused"


def test_agg_roofline_takes_the_whole_aggregation_program():
    """The share counts the bytes of the calls made against the device
    time of the ``jit__fused`` program, pad and slice included: the
    kernel's own event reads its stack from VMEM."""
    import counting
    from harness import SessionStats, Window

    host = _plane("/host:CPU", python=[_ev("bench.window", 0, 10**6)])
    dev = _plane(
        "/device:TPU:0",
        **{"XLA Ops": [_ev("%pad_bitcast_fusion = f32[1,8,1152,128]",
                           1000, 6000),
                       _ev("%_fused.1 = custom-call", 7000, 1500),
                       _ev("%slice.0 = f32[136672]", 8500, 1500)],
           "XLA Modules": [_ev("jit__fused(4)", 1000, 9000)]})
    win = Window(trace=tr.reduce_planes([host, dev]),
                 traced=SessionStats(agg_sizes=[8]))
    peaks = counting.peaks("TPU v5 lite")
    run = Run(win, 1.0, peaks, 136672)
    import harness
    read = harness.load_module("layers", "agg_roofline").read
    need_s = counting.aggregation_bytes(136672, 8) / peaks["hbm_bytes_per_s"]
    assert read(run) == pytest.approx(100.0 * need_s / 9e-6)
    assert 0 < read(run) <= 100
    win.traced = SessionStats()
    assert read(run) is None


CHIP_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "data", "cnn-tiny-trace.json.gz")


def _chip_trace():
    with gzip.open(CHIP_TRACE, "rt") as f:
        doc = json.load(f)
    planes = [_plane(p["name"], **{
        line["name"]: [_ev(n, s, d, **({"hlo_module": m} if m else {}))
                       for n, s, d, m in line["events"]]
        for line in p["lines"]}) for p in doc["planes"]]
    return doc, planes


def test_a_trace_recorded_on_the_chip():
    """The names the readers look for are the ones a TPU trace has, and
    the shares they read from it lie within (0, 100]."""
    import counting
    import harness
    from harness import SessionStats, Window

    doc, planes = _chip_trace()
    r = tr.reduce_planes(planes)
    assert r is not None and r.devices == 1
    assert 0 < r.busy_ns < r.window_ns
    assert r.programs(r"^jit__fused$") > 0
    assert r.programs(r"^jit_(step|train_scan)$") > 0
    assert r.span_durations("bench.aggregate")
    assert r.span_ns("bench.result") > 0
    assert r.gaps and all(n == "host" or n.startswith(tr.SPAN_PREFIX)
                          for n, _ in r.gaps)
    win = Window(trace=r, traced=SessionStats(rounds=doc["rounds"],
                                              agg_sizes=doc["agg_sizes"]))
    run = Run(win, 1.0, counting.peaks("TPU v5 lite"), doc["n_params"])
    for name in ("agg_roofline", "idle_share", "host_loop_share",
                 "eval_share"):
        value = harness.load_module("layers", name).read(run)
        assert value is not None and 0 < value <= 100, (name, value)
    assert harness.load_module("layers", "train_dev_ms_per_round").read(
        run) > 0
