#!/usr/bin/env python3
"""Read the comparison numbers of the program, of the control and of the
planted faults, for many seeds in one process, at a cell's own size, on
the chip.

    python3 benchmarks/chip/control.py --workload cnn-modest-diurnal \\
        --seeds 101,102,103 --seconds 10

For each seed the cell's inputs are built from the seed, a window of
``--seconds`` runs through the timed path, and its sampled answers are
compared with the plain reference as the program produced them and as
each stand-in produces them: the control (the reference in the config's
control dtype) and the half-batch fault (the reference trained on half of
every batch). ``exact`` is the program against the reference in exact
float32, for the record; ``pad_witness`` evaluates each sampled model on
a test set whose size is no multiple of the program's evaluation batch
(see :func:`pad_witness`). One JSON line per seed goes to stdout, each
number as the worst over the sampled answers and as the list of them.
The limits in the config files are set between the program's largest
reading and the smallest of the control's and the faults'. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import harness
from run_cell import find_chips

STAND_INS = ("control", "half_batch")
EVAL_BATCH = 64        # the rows JaxTask.evaluate_many takes per batch


def per_leaf(cell, recorder, against=None) -> dict:
    """Per leaf of the model, the largest :func:`harness.change_gaps` over
    the sampled jobs, of the program or of a stand-in's ``train``; and,
    each the largest over the jobs, ``"_median"``, the median leaf's (the
    number ``train_gap`` compares);
    ``"_whole"``, the same gap of norms over the whole model; ``"_diff"``,
    the worst leaf of the norm of the difference over that of the
    reference's change."""
    import numpy as np

    out = {}

    def keep(k, v):
        out[k] = max(out.get(k, 0.0), v)

    for params, client, bs, epochs, seed, lr_scale, result in \
            harness.sampled_jobs(recorder):
        start = harness.leaves(params)
        train = dict(cell.config["train"])
        train["lr"] = train["lr"] * lr_scale
        kw = dict(batch_size=bs, epochs=epochs, seed=seed, train_cfg=train,
                  model_cfg=harness.model_cfg(cell))
        want = harness.reference_on(cell, cell.reference.train, start,
                                    client.x, client.y, **kw)
        got = harness.leaves(result) if against is None else \
            against(start, client.x, client.y, **kw)
        gaps = harness.change_gaps(got, want, start)
        for k, v in gaps.items():
            keep(k, v)
        keep("_median", float(np.median(list(gaps.values()))))
        cat = {"all": np.concatenate([v.ravel() for v in want.values()])}
        keep("_whole", max(harness.change_gaps(
            {"all": np.concatenate([got[k].ravel() for k in want])}, cat,
            {"all": np.concatenate([start[k].ravel() for k in want])}
        ).values()))
        change = {k: want[k] - start[k] for k in want}
        keep("_diff", harness.leaf_gap(got, want, change))
    return out


def exact_train_gap(cell, recorder) -> float:
    """The program's training gap against the reference in exact float32
    (no operand rounding)."""
    return max(harness.train_gaps(cell, harness.sampled_jobs(recorder),
                                  exact=True))


def pad_witness(cell, recorder, extra: int = 16) -> list:
    """The test set with its first ``extra`` images appended, so that the
    last of the program's evaluation batches is short. Per sampled
    evaluated model: the program's loss there (``evaluate_models``, the
    timed path's call), the reference's plain mean, and the reference's
    mean with that short batch padded by copies of its first row, each
    weighted as a real row of the batch mean: what the program's padding
    gives."""
    import jax.numpy as jnp
    import numpy as np

    from repro.data.loader import ClientDataset
    from repro.engine.flat import FlatModel

    x, y = cell.test
    x, y = np.concatenate([x, x[:extra]]), np.concatenate([y, y[:extra]])
    n = len(x)
    r = n % EVAL_BATCH
    cfg = harness.model_cfg(cell)
    out = []
    for model, _ in recorder.evals.items:
        p = harness.leaves(model)

        def ref(lo, hi):
            return harness.reference_on(
                cell, cell.reference.evaluate, p, x[lo:hi], y[lo:hi],
                model_cfg=cfg)["loss"]

        plain = ref(0, n)
        short, first = ref(n - r, n) * r, ref(n - r, n - r + 1)
        padded = (plain * n - short + r / EVAL_BATCH *
                  (short + (EVAL_BATCH - r) * first)) / n
        on_chip = FlatModel(jnp.asarray(model.buffer), model.spec)
        got = cell.task.evaluate_many([on_chip], ClientDataset(x, y))[0]
        out.append({"program": got["loss"], "plain": plain,
                    "padded": padded})
    return out


def read_seed(cell, recorder) -> dict:
    """Every reading of one seed's window."""
    limits = cell.config["limits"]

    def worst_and_all(against=None):
        lists = harness.gap_lists(cell, recorder, against)
        return ({k: max(v) for k, v in lists.items() if k in limits and v},
                lists)

    out = {}
    out["program"], out["program_all"] = worst_and_all()
    for kind in STAND_INS:
        out[kind], out[kind + "_all"] = worst_and_all(
            harness.stand_in(cell, kind))
    out["exact_train_gap"] = exact_train_gap(cell, recorder)
    out["train_leaves"] = per_leaf(cell, recorder)
    for kind in STAND_INS:
        out[kind + "_leaves"] = per_leaf(
            cell, recorder, harness.stand_in(cell, kind)["train"])
    out["pad_witness"] = pad_witness(cell, recorder)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    m = harness.manifest()
    w = harness.workload(m, args.workload)
    devices = find_chips(w["chips"])
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    compiles = harness.CompileCounter()
    config = harness.load_json("configs", w["config"])
    traffic = harness.load_json("traffic", w["traffic"])
    task = None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = harness.build_cell(w["name"], config, traffic, seed,
                                  task=task, devices=devices)
        if task is None:
            harness.warm_shapes(cell)
            task = cell.task
        recorder = harness.Recorder(seed, harness.sample_counts(config))
        win = harness.run_window(cell, args.seconds, recorder, compiles,
                                 log=lambda *a: print(*a, file=sys.stderr))
        line = {"workload": w["name"], "seed": seed,
                "sessions": win.attempted, "failed": win.failed,
                "rounds": win.rounds, "window_compiles": win.compiles,
                "window_s": win.seconds}
        line.update(read_seed(cell, recorder))
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
