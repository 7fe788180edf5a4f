#!/usr/bin/env python3
"""Smoke test of the MoDeST session path on a TPU.

    python chip_smoke.py              # one chip: sessions + fused kernels
    python chip_smoke.py --chips 4    # four chips: sharded vs batched engine

One chip (the default):

* session phase — two ``ModestSession`` runs of the paper CNN at its
  published width (LeNet (6, 16) on 32×32×3, N = 136,672) over 50 nodes,
  s = 10, a = 2: one with sf = 1, one with sf = 0.8 so that aggregations
  see fewer than s models. Both must complete at least 3 rounds with
  finite losses and accuracies, and aggregate through the Pallas kernel.
* kernel phase — the fused one-pass, aggregate→quantize and masked
  (secure-aggregation) kernels at the same width for P in {2, 5, 10},
  each checked against ``repro.kernels.ref`` and timed on the device; the
  compiled program must hold a ``tpu_custom_call`` (a Pallas kernel, not
  interpret mode and not the jnp path).

``--chips 4`` runs only the sharded engine (``engine="sharded"``, the
flat buffers sharded over a 4-chip mesh) against the same session with
the batched engine on one chip, and checks the contract of
docs/SHARDING.md: same simulated trajectory, fp32-close training metrics,
bit-identical int8 codes and scales.

There is no CPU fallback: without a TPU the script exits non-zero. Any
failed phase makes it exit non-zero. On success the last line of stdout
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Compiled programs are cached where ``JAX_COMPILATION_CACHE_DIR`` says,
else in ``.jax_cache/`` at the repo root; a second run compiles less.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_NODES, SAMPLE, AGGREGATORS, BATCH = 50, 10, 2, 20
DURATION = 15.0           # simulated seconds per session (~12 rounds)
MIN_ROUNDS = 3
KERNEL_P = (2, 5, 10)
TIMING_REPS = 20


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit's compile seconds are its read time)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"compile: backend_compile_seconds={self.seconds} "
                f"compiles={self.compiles} "
                f"cache_requests={self.cache_requests} "
                f"cache_hits={self.cache_hits}")


def find_tpu():
    """The devices, or exit non-zero when JAX finds no TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{devices[0].platform!r}); there is no CPU fallback")
    return devices


def _libtpu_version() -> str:
    from importlib import metadata

    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return "unknown"


def _finite_history(result) -> list:
    """(round, loss, accuracy) per evaluated round; raises on non-finite."""
    rows = [(h["round"], h["loss"], h["accuracy"]) for h in result.history
            if "loss" in h]
    assert rows, "no evaluated rounds"
    for k, loss, acc in rows:
        assert math.isfinite(loss) and math.isfinite(acc), (k, loss, acc)
    return rows


def _session(task, data, *, sf: float, seed: int, engine=None):
    from repro.config import ModestConfig, TrainConfig
    from repro.sim.runner import ModestSession

    mcfg = ModestConfig(n_nodes=N_NODES, sample_size=SAMPLE,
                        n_aggregators=AGGREGATORS, success_fraction=sf,
                        seed=seed)
    return ModestSession(n_nodes=N_NODES, mcfg=mcfg,
                         tcfg=TrainConfig(batch_size=BATCH, seed=seed),
                         task=task, data=data, seed=seed,
                         eval_every_rounds=1, engine=engine)


def _device_ms(fn) -> float:
    """Median wall milliseconds of ``fn`` ending in block_until_ready,
    after one warm-up call (which compiles)."""
    import jax

    jax.block_until_ready(fn())
    times = []
    for _ in range(TIMING_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e3


def session_phase(seed: int) -> None:
    import repro.kernels.ops as ops
    from repro.data import make_classification_task
    from repro.models.tasks import cnn_task

    task = cnn_task()
    assert task.flat_spec.n == 136672, task.flat_spec.n
    data = make_classification_task(N_NODES, seed=seed)
    assert not ops._default_interpret(), "Pallas would run in interpret mode"

    kernel_calls = [0]
    real_kernel = ops.aggregate_flat_onepass

    def counted(*a, **k):
        kernel_calls[0] += 1
        return real_kernel(*a, **k)

    for sf in (1.0, 0.8):
        sizes = []
        real_aggregate = task.aggregate

        def recording(models, weights=None, **kw):
            sizes.append(len(models))
            return real_aggregate(models, weights, **kw)

        task.aggregate = recording
        ops.aggregate_flat_onepass = counted
        calls0 = kernel_calls[0]
        try:
            t0 = time.perf_counter()
            result = _session(task, data, sf=sf, seed=seed).run(DURATION)
            wall = time.perf_counter() - t0
        finally:
            del task.aggregate
            ops.aggregate_flat_onepass = real_kernel
        rows = _finite_history(result)
        calls = kernel_calls[0] - calls0
        print(f"session sf={sf}: rounds={result.rounds_completed} "
              f"wall_seconds={wall} aggregations={len(sizes)} "
              f"P_seen={sorted(set(sizes))} pallas_kernel_calls={calls}")
        print(f"session sf={sf}: first (round, loss, accuracy)={rows[0]} "
              f"last={rows[-1]}")
        assert result.rounds_completed >= MIN_ROUNDS, result.rounds_completed
        assert calls == len(sizes) > 0, (calls, len(sizes))
        if sf < 1:
            assert min(sizes) < SAMPLE, f"sf={sf} never aggregated < s models"


def kernel_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.engine.flat import FlatModel
    from repro.kernels import fused, ref
    from repro.kernels.ops import (aggregate_flatmodel,
                                   masked_aggregate_flatmodel)
    from repro.models.tasks import cnn_task
    from repro.secureagg import PairwiseMasker

    spec = cnn_task().flat_spec
    n = spec.n
    pad = (-n) % fused.SUBTILE
    rng = np.random.default_rng(seed)
    int_mask = jnp.asarray(np.arange(n) % 97 == 0, jnp.float32)
    for p in KERNEL_P:
        x = jnp.asarray(rng.standard_normal((p, n)), jnp.float32)
        w = jnp.asarray(rng.random(p) + 0.1, jnp.float32)
        models = [FlatModel(x[i], spec) for i in range(p)]
        with jax.default_matmul_precision("highest"):
            want = ref.aggregate_ref(x, w)

        # plain one-pass, through the public entry point
        mean = aggregate_flatmodel(models, w, spec=spec).buffer
        err = float(jnp.max(jnp.abs(mean - want)))
        assert np.allclose(mean, want, rtol=1e-5, atol=1e-6), ("onepass", p,
                                                                err)
        # in-kernel rounding of integer lanes
        rounded = fused.aggregate_flat_onepass(x, w, int_mask)
        want_r = jnp.where(int_mask > 0, jnp.round(want), want)
        assert np.allclose(rounded, want_r, rtol=1e-5, atol=1e-6), p
        assert np.array_equal(rounded[int_mask > 0],
                              jnp.round(rounded[int_mask > 0])), p

        # fused aggregate→quantize: codes/scales of quantize_ref(mean)
        qfm, codes, scales = aggregate_flatmodel(models, w, spec=spec,
                                                 quantize=True)
        assert np.array_equal(qfm.buffer, mean), ("quantize mean", p)
        want_q, want_s = ref.quantize_ref(jnp.pad(qfm.buffer, (0, pad)))
        diff = np.abs(np.asarray(codes, np.int32)
                      - np.asarray(want_q[:n], np.int32))
        assert diff.max() <= 1 and (diff > 0).sum() <= n * 1e-4, (
            "codes", p, int(diff.max()), int((diff > 0).sum()))
        assert np.allclose(scales, want_s, rtol=1e-6, atol=0), ("scales", p)

        # secure aggregation: sealed rows unmask in-kernel, bit-identical
        masker = PairwiseMasker(seed)
        roster = tuple(f"n{i}" for i in range(p))
        sealed = [masker.seal(m, roster[i], 7, roster, spec.nbytes)
                  for i, m in enumerate(models)]
        seeds, signs = masker.unmask_matrices(
            sealed, {r: masker.secret(r, 7) for r in roster})
        payloads = [s.payload for s in sealed]
        mm = masked_aggregate_flatmodel(payloads, w, seeds=seeds, signs=signs,
                                        spec=spec).buffer
        mq, mc, ms = masked_aggregate_flatmodel(
            payloads, w, seeds=seeds, signs=signs, spec=spec, quantize=True)
        assert np.array_equal(mm, mean), ("masked mean", p)
        assert np.array_equal(mq.buffer, mean), ("masked quantize mean", p)
        assert np.array_equal(mc, codes) and np.array_equal(ms, scales), p
        print(f"kernels P={p}: match ref (max |mean - ref|={err}, "
              f"codes off by one={int((diff > 0).sum())}/{n}); "
              f"masked == plain bit for bit")

        y = jnp.stack([fused.bits(s.payload.buffer) for s in sealed])
        seeds_d = jnp.asarray(seeds, jnp.uint32)
        signs_d = jnp.asarray(signs, jnp.int32)
        zeros = jnp.zeros((n,), jnp.float32)
        timed = {
            "onepass": lambda: fused.aggregate_flat_onepass(x, w, zeros),
            "quantize": lambda: fused.aggregate_quantize_flat(x, w, zeros),
            "masked": lambda: fused.unmask_aggregate_flat(
                y, w, zeros, seeds=seeds_d, signs=signs_d),
            "masked_quantize": lambda: fused.unmask_aggregate_quantize_flat(
                y, w, zeros, seeds=seeds_d, signs=signs_d),
        }
        for name, fn in timed.items():
            print(f"kernel {name} P={p} N={n} tile={fused.tile_for(n, p)}: "
                  f"device_ms={_device_ms(fn)} (median of {TIMING_REPS}, "
                  f"block_until_ready)")


def evidence_phase(seed: int) -> None:
    """The programs the session's aggregation runs hold a compiled Pallas
    kernel: compile them for this chip and look for its custom call."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import fused
    from repro.models.tasks import cnn_task

    n = cnn_task().flat_spec.n
    for p in KERNEL_P:
        for quantize, masked in ((False, False), (True, True)):
            args = [jax.ShapeDtypeStruct((p, n), jnp.float32),
                    jax.ShapeDtypeStruct((p,), jnp.float32),
                    jax.ShapeDtypeStruct((n,), jnp.float32)]
            if masked:
                args += [jax.ShapeDtypeStruct((p, p), jnp.uint32),
                         jax.ShapeDtypeStruct((p, p), jnp.int32),
                         jax.ShapeDtypeStruct((1,), jnp.int32)]
            text = fused._fused.lower(
                *args, tile=fused.tile_for(n, p),
                n_valid=n if masked else None, quantize=quantize,
                interpret=False).compile().as_text()
            hits = text.count("tpu_custom_call")
            assert hits > 0, ("no Pallas kernel in the compiled program", p)
            print(f"evidence P={p} quantize={quantize} masked={masked}: "
                  f"the compiled aggregation holds {hits} tpu_custom_call "
                  f"(a compiled Pallas kernel)")


def sharded_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.data import make_classification_task
    from repro.engine import FlatModel, MeshEngine
    from repro.kernels.ops import (aggregate_flatmodel,
                                   masked_aggregate_flatmodel)
    from repro.models.tasks import cnn_task
    from repro.secureagg import PairwiseMasker

    assert jax.device_count() == 4, jax.device_count()
    task = cnn_task()
    data = make_classification_task(N_NODES, seed=seed)
    runs = {}
    for engine in ("sharded", "batched"):
        session = _session(task, data, sf=1.0, seed=seed, engine=engine)
        t0 = time.perf_counter()
        result = session.run(DURATION)
        wall = time.perf_counter() - t0
        runs[engine] = (session, result, _finite_history(result))
        print(f"engine={engine} ({type(session.engine).__name__}): "
              f"rounds={result.rounds_completed} wall_seconds={wall} "
              f"last (round, loss, accuracy)={runs[engine][2][-1]}")
    sh, ba = runs["sharded"], runs["batched"]
    assert isinstance(sh[0].engine, MeshEngine)
    assert sh[0].engine.shardings.n_shards == 4
    assert sh[1].rounds_completed == ba[1].rounds_completed >= MIN_ROUNDS
    assert sh[1].usage["total_bytes"] == ba[1].usage["total_bytes"]
    assert [r[0] for r in sh[2]] == [r[0] for r in ba[2]]
    drift = max(max(abs(a[1] - b[1]), abs(a[2] - b[2]))
                for a, b in zip(sh[2], ba[2]))
    print(f"sharded vs batched: same rounds, bytes and eval rounds; "
          f"max |loss/accuracy drift|={drift}")
    assert drift < 0.05, drift

    spec = task.flat_spec
    fs = sh[0].engine.shardings
    rng = np.random.default_rng(seed)
    models = [spec.unpack(np.asarray(rng.standard_normal(spec.n), np.float32))
              for _ in range(5)]
    w = list(rng.random(5) + 0.1)
    one = aggregate_flatmodel(models, w, spec=spec, quantize=True)
    four = aggregate_flatmodel(models, w, spec=spec, quantize=True,
                               shardings=fs)
    mean_err = float(jnp.max(jnp.abs(jnp.asarray(one[0].buffer)
                                     - jnp.asarray(four[0].buffer))))
    assert np.array_equal(one[1], four[1]), "int8 codes differ across shards"
    assert np.array_equal(one[2], four[2]), "scales differ across shards"
    assert mean_err <= 1e-6, mean_err

    masker = PairwiseMasker(seed)
    roster = tuple(f"n{i}" for i in range(5))
    sealed = [masker.seal(FlatModel(spec.pack(m), spec), roster[i], 7,
                          roster, spec.nbytes) for i, m in enumerate(models)]
    seeds, signs = masker.unmask_matrices(
        sealed, {r: masker.secret(r, 7) for r in roster})
    masked = masked_aggregate_flatmodel(
        [s.payload for s in sealed], w, seeds=seeds, signs=signs, spec=spec,
        quantize=True, shardings=fs)
    assert np.array_equal(masked[1], four[1]), "masked codes differ"
    assert np.array_equal(masked[0].buffer, four[0].buffer), "masked mean"
    print(f"aggregation 1 chip vs 4 shards: int8 codes and scales "
          f"bit-identical, max |mean diff|={mean_err}; masked == plain on "
          f"4 shards")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-engine comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not __debug__:
        sys.exit("chip_smoke: the checks are assert statements; run "
                 "without python -O")

    devices = find_tpu()
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    stats = CompileStats()
    print(f"device: platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)}")
    print(f"versions: jax={jax.__version__} libtpu={_libtpu_version()}")
    print(f"compile cache: {cache_dir}")

    phases = ([("sharded", sharded_phase)] if args.chips == 4
              else [("session", session_phase), ("kernels", kernel_phase),
                    ("evidence", evidence_phase)])
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase(args.seed)
        except Exception:
            traceback.print_exc()
            failed.append(name)
        print(f"phase {name}: {'FAILED' if name in failed else 'ok'} "
              f"wall_seconds={time.perf_counter() - t0}")
    print(stats.line())
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
