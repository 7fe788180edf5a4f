import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes and record memory / cost / collective analyses.

MUST be run as its own process (the two lines above must execute before
any other jax import — jax locks the device count on first init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]

Artifacts: benchmarks/artifacts/dryrun/{arch}__{shape}__{mesh}.json with
  memory_analysis (per-device bytes), cost_analysis (flops/bytes),
  collective bytes by kind (parsed from compiled HLO), timings.
Existing artifacts are skipped unless --force.
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.config import SHAPES, TrainConfig, V5E
from repro.core.distributed import DistributedTrainer, Server
from repro.launch.mesh import make_production_mesh, mesh_config
from repro.sharding import ShardingPolicy, input_specs
from repro.utils.hlo import collective_bytes

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "benchmarks", "artifacts", "dryrun")

# Grad-accumulation microbatching per arch for train_4k (E axis of the
# batch): keeps the remat carry within HBM. Chosen by napkin math in
# EXPERIMENTS.md §Dry-run; tuned further in §Perf.
TRAIN_MICRO = {
    "llama3-405b": 16,
    "arctic-480b": 8,
    "gemma2-27b": 4,
    "starcoder2-15b": 4,
    "qwen3-moe-30b-a3b": 4,
    "llava-next-mistral-7b": 4,
    "whisper-large-v3": 2,
    "hymba-1.5b": 1,
    "rwkv6-1.6b": 1,
    "tinyllama-1.1b": 1,
}

# long_500k needs sub-quadratic attention: dense/moe/audio archs without a
# native window get an explicit sliding-window variant (DESIGN.md §4).
LONG_CTX_WINDOW = 8192


def effective_config(arch: str, shape_name: str):
    cfg = configs.get_config(arch)
    if shape_name == "long_500k" and cfg.window == 0 and cfg.family in (
            "dense", "moe", "audio", "vlm"):
        cfg = cfg.with_(window=LONG_CTX_WINDOW)
    return cfg


def _micro_batch(arch: str, shape, n_participants: int, micro_override=None):
    micro = micro_override or TRAIN_MICRO.get(arch, 1)
    per_part = max(shape.global_batch // max(n_participants, 1), 1)
    micro = min(micro, per_part)
    return micro, max(per_part // micro, 1)


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool,
               strategy: str = "modest", verbose: bool = True,
               extra_cfg=None, agg_dtype: str = "float32",
               micro_override=None, accumulate: bool = False) -> dict:
    shape = SHAPES[shape_name]
    cfg = effective_config(arch, shape_name)
    if extra_cfg:
        cfg = cfg.with_(**extra_cfg)
    mcfg = mesh_config(multi_pod=multi_pod)
    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = ShardingPolicy(cfg, mcfg)
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mcfg.shape)),
        "strategy": strategy if shape.kind == "train" else "serve",
        "participants": policy.n_participants,
        "window": cfg.window,
        "overrides": dict(extra_cfg or {}),
    }
    t0 = time.time()  # noqa: DL002(lower/compile wall timing for the dry-run record)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            micro, b_micro = _micro_batch(arch, shape, policy.n_participants,
                                          micro_override)
            record["micro_steps"], record["micro_batch"] = micro, b_micro
            trainer = DistributedTrainer(
                cfg, TrainConfig(optimizer="sgd", agg_dtype=agg_dtype),
                mcfg, strategy=strategy, mesh=mesh)
            state_t = trainer.abstract_state()
            batch_t = _train_batch_template(cfg, shape, policy, micro, b_micro)
            weights_t = jax.ShapeDtypeStruct((policy.n_participants,),
                                             jnp.float32)
            record["accumulate"] = accumulate
            step = trainer.jit_train_step(state_t, batch_t,
                                          accumulate=accumulate)
            lowered = step.lower(state_t, batch_t, weights_t)
        else:
            shard_seq = (shape.name == "long_500k")
            server = Server(cfg, mcfg, mesh=mesh, shard_seq=shard_seq)
            params_t = jax.eval_shape(server.model.init, jax.random.key(0))
            max_len = _cache_len(cfg, shape)
            cache_t = server.abstract_cache(shape.global_batch, max_len)
            if shape.kind == "prefill":
                batch_t = input_specs(cfg, shape, policy)
                fn = server.jit_prefill(params_t, batch_t, cache_t)
                lowered = fn.lower(params_t, batch_t, cache_t)
            else:
                tok_t = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
                fn = server.jit_decode(params_t, cache_t)
                lowered = fn.lower(params_t, tok_t, cache_t)

        record["lower_s"] = round(time.time() - t0, 2)  # noqa: DL002(lower/compile wall timing for the dry-run record)
        t1 = time.time()  # noqa: DL002(lower/compile wall timing for the dry-run record)
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)  # noqa: DL002(lower/compile wall timing for the dry-run record)

    try:
        mem = compiled.memory_analysis()
        record["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
        if hasattr(mem, "serialized_size_in_bytes"):
            record["memory"]["serialized_size_in_bytes"] = int(
                mem.serialized_size_in_bytes)
    except Exception as e:  # pragma: no cover
        record["memory_error"] = str(e)

    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        record["cost"] = {k: float(v) for k, v in ca.items()
                          if isinstance(v, (int, float)) and (
                              k in ("flops", "bytes accessed")
                              or k.startswith("bytes accessed"))}
    except Exception as e:  # pragma: no cover
        record["cost_error"] = str(e)

    hlo = compiled.as_text()
    record["collectives"] = collective_bytes(hlo)
    # SPMD HLO shapes are PER-DEVICE; the brief's roofline formula divides
    # global collective bytes by chips, so scale up here (documented in
    # EXPERIMENTS.md §Roofline methodology).
    record["collectives"]["per_device_bytes"] = record["collectives"]["total_bytes"]
    record["collectives"]["total_bytes"] *= mcfg.n_devices
    from repro.roofline import analytic_terms
    record["roofline"] = analytic_terms(
        cfg, shape,
        n_participants=policy.n_participants,
        local_steps=record.get("micro_steps", 1),
        collective_total_bytes=record["collectives"]["total_bytes"],
        chips=mcfg.n_devices)
    # raw (while-body-once) numbers kept for reference
    record["roofline"]["raw_hlo_flops"] = record.get("cost", {}).get("flops")
    record["roofline"]["raw_hlo_bytes"] = record.get("cost", {}).get(
        "bytes accessed")
    if verbose:
        _print_summary(record)
    return record


def _train_batch_template(cfg, shape, policy, micro, b_micro):
    sd = jax.ShapeDtypeStruct
    i32, bf = jnp.int32, jnp.dtype(cfg.param_dtype)
    Pn = policy.n_participants
    batch = {
        "tokens": sd((Pn, micro, b_micro, shape.seq_len), i32),
        "labels": sd((Pn, micro, b_micro, shape.seq_len), i32),
    }
    if cfg.family == "audio":
        batch["frames"] = sd((Pn, micro, b_micro, cfg.n_frames, cfg.d_model), bf)
    if cfg.family == "vlm":
        n_img = cfg.image_tokens * cfg.anyres_tiles
        batch["image_embeds"] = sd((Pn, micro, b_micro, n_img, cfg.d_model), bf)
    return batch


def _cache_len(cfg, shape):
    max_len = shape.seq_len
    if cfg.family == "vlm":
        max_len += cfg.image_tokens * cfg.anyres_tiles
    return max_len


def _print_summary(r: dict) -> None:
    rl = r.get("roofline", {})
    mem = r.get("memory", {})
    tmp = mem.get("temp_size_in_bytes", 0)
    arg = mem.get("argument_size_in_bytes", 0)
    print(f"[dryrun] {r['arch']:24s} {r['shape']:12s} mesh={r['mesh']:10s} "
          f"compile={r.get('compile_s', 0):7.1f}s "
          f"flops={rl.get('flops', 0):.3e} "
          f"coll={r['collectives']['total_bytes']:.3e}B "
          f"args/dev={arg / 1e9:.2f}GB temp/dev={tmp / 1e9:.2f}GB "
          f"dom={rl.get('dominant')}")
    print(f"  memory_analysis: {mem}")
    print(f"  cost_analysis(raw, while-once): {r.get('cost')}")
    print(f"  collectives (trip-aware): {r['collectives']['bytes']}")
    print(f"  roofline: compute={rl.get('compute_s', 0):.4f}s "
          f"memory={rl.get('memory_s', 0):.4f}s "
          f"collective={rl.get('collective_s', 0):.4f}s "
          f"useful={rl.get('useful_flop_ratio', 0):.3f}")


def artifact_path(arch, shape_name, multi_pod, strategy="modest", tag=""):
    mesh = "2x16x16" if multi_pod else "16x16"
    suffix = f"__{tag}" if tag else ""
    return os.path.abspath(os.path.join(
        ARTIFACT_DIR, f"{arch}__{shape_name}__{mesh}__{strategy}{suffix}.json"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--strategy", default="modest",
                    choices=["modest", "fedavg", "dsgd", "local"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="artifact suffix for perf expts")
    ap.add_argument("--agg-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--micro", type=int, default=None,
                    help="override grad-accum micro steps (perf expts)")
    ap.add_argument("--accumulate", action="store_true",
                    help="E axis = grad accumulation (one update per round)")
    ap.add_argument("--set", nargs="*", default=[],
                    help="cfg overrides key=value (perf experiments)")
    args = ap.parse_args()

    from repro.config import parse_overrides
    overrides = parse_overrides(args.set)

    archs = configs.ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    failures = []
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                path = artifact_path(arch, shape_name, mp, args.strategy,
                                     args.tag)
                if os.path.exists(path) and not args.force:
                    print(f"[dryrun] skip existing {os.path.basename(path)}")
                    continue
                try:
                    rec = dryrun_one(arch, shape_name, multi_pod=mp,
                                     strategy=args.strategy,
                                     extra_cfg=overrides,
                                     agg_dtype=args.agg_dtype,
                                     micro_override=args.micro,
                                     accumulate=args.accumulate)
                    with open(path, "w") as fh:
                        json.dump(rec, fh, indent=1)
                except Exception as e:
                    failures.append((arch, shape_name, mp, repr(e)))
                    print(f"[dryrun] FAIL {arch} {shape_name} mp={mp}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall dry-runs OK")


if __name__ == "__main__":
    main()
