"""Kernel + engine micro-benchmarks → ``BENCH_kernels.json``.

Three perf claims of the FlatModel engine (PR 4), each measured against
its pre-engine baseline on the paper CNN:

* **Whole-model one-pass aggregation** vs the per-leaf path (one
  ``pallas_call`` + ravel/stack/pad per pytree leaf). The engine's
  default on CPU is the jnp one-pass contraction (same single pass over
  the ``(P, N)`` stack, no Pallas-interpreter overhead); the Pallas
  kernel — what TPU runs — is also timed in interpret mode for
  validation. On this CPU container absolute times are NOT
  TPU-representative; the analytic HBM roofline is attached to each row.
* **Fused aggregate→quantize** vs per-leaf aggregation followed by
  per-leaf quantization.
* **Vmapped cohort training** (S clients as one ``(S, N)`` flat batch,
  B dispatches instead of S·B) vs the sequential per-node path, at the
  paper's CIFAR-shape operating point and at a dispatch-bound small
  shape.

A fourth claim rides along since the sharded engine (docs/SHARDING.md):
the per-shard fused aggregate→quantize path produces **bit-identical**
int8 codes on 1 device and sharded over every device present — the
``sharded`` rows carry a codes checksum from each device count so the
artifact records the equivalence, not just the timing. Both rows run in
this process (a child would need the chip this process holds); run
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to get a
multi-device row on the CPU.

A fifth rides along since secure aggregation (docs/SECUREAGG.md): the
``sharded`` rows also time the fused unmask→aggregate→quantize path
against the plain fused path (``secure_overhead_x``) and record its
codes checksum — masked and plain must be bit-identical at every device
count when all senders survive.

``--quick`` runs the CI-sized subset and still emits the full JSON.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, out_path
from repro.data.loader import ClientDataset
from repro.engine.cohort import BatchedEngine
from repro.engine.flat import FlatModel
from repro.kernels import (aggregate_flatmodel, aggregate_pytree,
                           quantize_flat)
from repro.kernels.fused import tile_for
from repro.models.tasks import cnn_task
from repro.roofline import aggregation_roofline


def _time(fn, reps=7):
    out = fn()
    jax.block_until_ready(jax.tree.leaves(out))
    ts = []
    for _ in range(reps):
        t0 = time.time()
        out = fn()
        jax.block_until_ready(jax.tree.leaves(out))
        ts.append(time.time() - t0)
    return float(np.median(ts)) * 1e3          # ms


def bench_aggregation(P: int, reps: int) -> dict:
    task = cnn_task()
    spec = task.flat_spec
    params = task.init_params(0)
    models = [jax.tree.map(lambda l: l + i * 0.01, params) for i in range(P)]
    fms = [FlatModel.pack(m, spec) for m in models]
    w = [1.0] * P

    ms_leaf = _time(lambda: aggregate_pytree(models, np.asarray(w)), reps)
    ms_one = _time(lambda: aggregate_flatmodel(
        fms, w, spec=spec, use_kernel=False).buffer, reps)
    ms_one_k = _time(lambda: aggregate_flatmodel(
        fms, w, spec=spec, use_kernel=True).buffer, reps)

    ms_leaf_q = _time(lambda: [
        quantize_flat(jnp.ravel(l))
        for l in jax.tree.leaves(aggregate_pytree(models, np.asarray(w)))],
        reps)
    ms_fused_q = _time(lambda: aggregate_flatmodel(
        fms, w, spec=spec, quantize=True, use_kernel=False)[1], reps)
    ms_fused_qk = _time(lambda: aggregate_flatmodel(
        fms, w, spec=spec, quantize=True, use_kernel=True)[1], reps)

    roof = aggregation_roofline(spec.n, P)
    roof_q = aggregation_roofline(spec.n, P, fused_quantize=True)
    return {
        "model": "paper-cnn", "n_params": spec.n, "leaves": len(spec.shapes),
        "P": P, "flat_tile": tile_for(spec.n, P),
        "per_leaf_ms": round(ms_leaf, 2),
        "onepass_engine_ms": round(ms_one, 2),
        "onepass_pallas_interpret_ms": round(ms_one_k, 2),
        "speedup_onepass": round(ms_leaf / ms_one, 2),
        "speedup_onepass_interpret": round(ms_leaf / ms_one_k, 2),
        "per_leaf_agg_then_quant_ms": round(ms_leaf_q, 2),
        "fused_agg_quant_engine_ms": round(ms_fused_q, 2),
        "fused_agg_quant_pallas_interpret_ms": round(ms_fused_qk, 2),
        "speedup_fused_quant": round(ms_leaf_q / ms_fused_q, 2),
        "speedup_fused_quant_interpret": round(ms_leaf_q / ms_fused_qk, 2),
        **{("roofline_" + k): v for k, v in roof.items()},
        "roofline_fusedq_onepass_tpu_us": roof_q["onepass_tpu_us"],
    }


def bench_cohort(S: int, reps: int, *, image=(32, 32, 3), samples=40,
                 batch_size=20, epochs=1, label="cifar") -> dict:
    task = cnn_task(cnn_image=image) if image != (32, 32, 3) else cnn_task()
    params = task.init_params(0)
    rng = np.random.default_rng(0)
    clients = [ClientDataset(
        rng.normal(size=(samples,) + image).astype(np.float32),
        rng.integers(0, 10, samples)) for _ in range(S)]
    engine = BatchedEngine(task)

    # warm both paths (compile is paid once per task, not per session)
    for i, c in enumerate(clients):
        engine.submit(str(i), 0, params, c, batch_size=batch_size,
                      epochs=epochs, seed=0)
    [engine.result(str(i), 0, params, clients[i], batch_size=batch_size,
                   epochs=epochs, seed=0) for i in range(S)]
    task.local_train(params, clients[0], batch_size=batch_size,
                     epochs=epochs, seed=0)

    # Interleave the two paths and compare best-of-reps: shared-container
    # load spikes inflate whichever path happens to be running, so the
    # minimum is the least-noise estimator of each path's true cost
    # (classic microbenchmark practice).
    seq_ts, bat_ts = [], []
    for rep in range(1, reps + 1):
        t0 = time.time()
        outs = [task.local_train(params, c, batch_size=batch_size,
                                 epochs=epochs, seed=rep) for c in clients]
        jax.block_until_ready(jax.tree.leaves(outs[-1]))
        seq_ts.append(time.time() - t0)
        t0 = time.time()
        for i, c in enumerate(clients):
            engine.submit(str(i), rep, params, c, batch_size=batch_size,
                          epochs=epochs, seed=rep)
        outs = [engine.result(str(i), rep, params, clients[i],
                              batch_size=batch_size, epochs=epochs,
                              seed=rep) for i in range(S)]
        jax.block_until_ready(outs[-1].buffer)
        bat_ts.append(time.time() - t0)
    seq_ms = float(np.min(seq_ts)) * 1e3
    bat_ms = float(np.min(bat_ts)) * 1e3
    ratio = seq_ms / bat_ms
    steps = len(task._padded_batches(clients[0], batch_size,
                                     epochs=epochs))
    return {
        "model": f"paper-cnn-{label}", "S": S, "batch_size": batch_size,
        "steps_per_client": steps, "image": list(image),
        "sequential_ms": round(seq_ms, 1),
        "vmapped_ms": round(bat_ms, 1),
        "speedup_vmapped": round(ratio, 2),
        "dispatches_sequential": S * steps,
        "dispatches_vmapped": steps,
    }


def _sharded_row(reps: int, sharded: bool) -> dict:
    """One aggregation row on one device, or sharded over every device
    present (``make_engine_mesh``; None on a single device)."""
    from repro.kernels.fused import shard_align
    from repro.launch.mesh import make_engine_mesh

    task = cnn_task()
    spec = task.flat_spec
    params = task.init_params(0)
    fms = [FlatModel.pack(jax.tree.map(lambda l: l + i * 0.01, params), spec)
           for i in range(5)]
    w = [1.0] * 5
    mesh = make_engine_mesh() if sharded else None
    shardings = spec.sharding(mesh) if mesh is not None else None
    shards = shardings.n_shards if shardings is not None else 1
    local_n = shard_align(spec.n, shards) // shards if shards > 1 else spec.n

    ms_one = _time(lambda: aggregate_flatmodel(
        fms, w, spec=spec, shardings=shardings).buffer, reps)
    _, codes, _ = aggregate_flatmodel(fms, w, spec=spec, quantize=True,
                                      shardings=shardings)
    ms_q = _time(lambda: aggregate_flatmodel(
        fms, w, spec=spec, quantize=True, shardings=shardings)[1], reps)

    # secure-agg overhead: the fused unmask→aggregate→quantize path
    # (docs/SECUREAGG.md) vs the plain fused path, same stack. Masking is
    # free by construction on the wire side; this row prices the in-kernel
    # PRG + uint32 unmask-add the aggregator pays, and the codes digest
    # doubles as the masked/plain bit-identity record per device count.
    from repro.kernels.ops import masked_aggregate_flatmodel
    from repro.secureagg import PairwiseMasker

    masker = PairwiseMasker(0)
    roster = tuple(f"n{i}" for i in range(len(fms)))
    sealed = [masker.seal(fm, roster[i], 7, roster, spec.nbytes)
              for i, fm in enumerate(fms)]
    secrets = {nid: masker.secret(nid, 7) for nid in roster}
    seeds, signs = masker.unmask_matrices(sealed, secrets)
    payloads = [sm.payload for sm in sealed]
    _, mcodes, _ = masked_aggregate_flatmodel(
        payloads, w, seeds=seeds, signs=signs, spec=spec, quantize=True,
        shardings=shardings)
    ms_mq = _time(lambda: masked_aggregate_flatmodel(
        payloads, w, seeds=seeds, signs=signs, spec=spec, quantize=True,
        shardings=shardings)[1], reps)

    return {
        "model": "paper-cnn", "P": 5, "devices": shards,
        "model_shards": shards,
        "padded_n": shard_align(spec.n, shards) if shards > 1 else spec.n,
        "local_tile": tile_for(local_n, 5),
        "onepass_ms": round(ms_one, 2),
        "fused_agg_quant_ms": round(ms_q, 2),
        "secure_fused_agg_quant_ms": round(ms_mq, 2),
        "secure_overhead_x": round(ms_mq / ms_q, 2),
        "codes_sha256": hashlib.sha256(
            np.asarray(codes).tobytes()).hexdigest()[:16],
        "secure_codes_sha256": hashlib.sha256(
            np.asarray(mcodes).tobytes()).hexdigest()[:16],
    }


def bench_sharded(reps: int) -> list[dict]:
    """1-device vs all-devices sharded aggregation rows (docs/SHARDING.md),
    both in this process. Forced host devices share one CPU threadpool,
    so there the rows validate the sharded path's *bit-identity*
    (matching ``codes_sha256``), not a speedup."""
    rows = [_sharded_row(reps, sharded=False)]
    if jax.device_count() > 1:
        rows.append(_sharded_row(reps, sharded=True))
    return rows


def run(quick: bool = True):
    reps = 5 if quick else 9
    agg_rows = [bench_aggregation(5, reps)]
    if not quick:
        agg_rows.append(bench_aggregation(8, reps))
    cohort_rows = [
        bench_cohort(5, reps, label="cifar"),
        # dispatch-bound regime: tiny per-step compute makes the S·B → B
        # dispatch collapse (and the fused whole-round scan) visible —
        # this is the regime the engine targets on fast accelerators,
        # where per-step compute is sub-ms even at CIFAR shapes.
        bench_cohort(5, reps + 4, image=(8, 8, 3), samples=64, batch_size=4,
                     epochs=3, label="8x8-dispatch-bound"),
    ]
    sharded_rows = bench_sharded(reps)
    artifact = {
        "meta": {
            "quick": quick,
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "note": ("CPU container: Pallas rows run in interpret mode "
                     "(validation, not TPU-representative); the engine's "
                     "CPU default is the jnp one-pass path, the TPU "
                     "default is the Pallas kernel. See docs/ENGINE.md."),
        },
        "aggregate": agg_rows,
        "cohort": cohort_rows,
        "sharded": sharded_rows,
        "headline": {
            "onepass_vs_per_leaf": agg_rows[0]["speedup_onepass"],
            "fused_agg_quant": agg_rows[0]["speedup_fused_quant"],
            "vmapped_cohort_s5": max(r["speedup_vmapped"]
                                     for r in cohort_rows),
            "sharded_codes_identical": len(
                {r["codes_sha256"] for r in sharded_rows}) == 1,
            "secure_agg_overhead_x": sharded_rows[0]["secure_overhead_x"],
            "secure_codes_identical": len(
                {sha for r in sharded_rows
                 for sha in (r["codes_sha256"],
                             r["secure_codes_sha256"])}) == 1,
        },
    }
    with open(out_path("BENCH_kernels.json"), "w") as fh:
        json.dump(artifact, fh, indent=2)
    print(f"wrote {out_path('BENCH_kernels.json')}")
    rows = agg_rows + cohort_rows
    emit([{k: v for k, v in r.items() if not isinstance(v, list)}
          for r in agg_rows], "kernels.csv")
    emit([{k: v for k, v in r.items() if not isinstance(v, list)}
          for r in cohort_rows], "kernels_cohort.csv")
    emit(sharded_rows, "kernels_sharded.csv")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized subset (same JSON artifact)")
    args = ap.parse_args()
    run(quick=args.quick)
