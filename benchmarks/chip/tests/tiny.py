"""Tiny versions of the cells, for CPU tests of the harness: the real
config and traffic files with their sizes cut so that a session runs in
seconds under ``JAX_PLATFORMS=cpu``."""

from __future__ import annotations

import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import harness  # noqa: E402

TINY_NODES = 6
# simulated seconds of a tiny session and of the warm-up session, by
# session kind: a few rounds each (a tiny D-SGD round is half a second)
TINY_SECONDS = {"dsgd": (5.0, 2.0)}


def tiny(workload_name: str, root: str = harness.REPO):
    """(config, traffic) of a cell of the benchmark at ``root``, cut to a
    CPU-test size."""
    base = os.path.join(root, "benchmarks", "chip")
    w = harness.workload(harness.manifest(root), workload_name)
    config = copy.deepcopy(harness.load_json("configs", w["config"], base))
    traffic = copy.deepcopy(harness.load_json("traffic", w["traffic"],
                                              base))
    config["dataset"].update(train=TINY_NODES * 45, test=128)
    session_s, warmup_s = TINY_SECONDS.get(traffic["session"], (40.0, 10.0))
    traffic.update(nodes=TINY_NODES, session_seconds=session_s,
                   warmup_seconds=warmup_s, eval_every_rounds=2)
    traffic.update(sample_size=3, pass_sessions=2)
    traffic["warm"] = {"max_group": 3, "max_agg": 3, "max_eval": 2}
    return config, traffic


def tiny_cell(workload_name: str, seed: int = 5):
    config, traffic = tiny(workload_name)
    return harness.build_cell(workload_name, config, traffic, seed)


def run_tiny(cell, seconds: float = 1.0):
    """Warm the cell's shapes, run a window and check it, as a run on the
    chip does after finding the chip: (window, recorder, checks)."""
    compiles = harness.CompileCounter()
    harness.warm_shapes(cell)
    recorder = harness.Recorder(cell.seed,
                                harness.sample_counts(cell.config))
    win = harness.run_window(cell, seconds, recorder, compiles)
    return win, recorder, harness.check(cell, recorder)
