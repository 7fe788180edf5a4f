"""Serving driver: batched prefill + token-by-token decode.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \\
        --batch 4 --prompt-len 32 --new-tokens 16 [--devices 8]

Runs the reduced config on CPU by default (the full configs are exercised
via the dry-run); with ``--devices N`` it builds a small (data, model) mesh
and runs the same sharded prefill/decode path the dry-run lowers.
"""

import os
import sys


def _device_flag(argv):
    """Extract the --devices value from raw argv, before argparse runs.

    The XLA host-device-count flag must be set before jax imports, so this
    scan cannot wait for argparse. Handles ``--devices N``, ``--devices=N``
    and a bare trailing ``--devices`` (returns None and lets argparse
    report the missing value instead of raising IndexError here).
    """
    for i, arg in enumerate(argv):
        if arg == "--devices":
            if i + 1 < len(argv):
                return argv[i + 1]
            return None
        if arg.startswith("--devices="):
            return arg.split("=", 1)[1]
    return None


_n = _device_flag(sys.argv[1:])
if _n is not None and _n.isdigit() and int(_n) > 0:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={_n}"
                               ).strip()

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--model-parallel", type=int, default=2)
    ap.add_argument("--full-size", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.config import MeshConfig
    from repro.core.distributed import Server

    cfg = configs.get_config(args.arch)
    if not args.full_size:
        cfg = configs.reduced(cfg)

    from repro.launch.mesh import make_mesh

    if args.devices:
        mp = args.model_parallel
        if mp <= 0 or jax.device_count() % mp != 0:
            raise SystemExit(
                f"[serve] device_count={jax.device_count()} is not divisible "
                f"by --model-parallel {mp}; pick a model-parallel degree "
                "that divides the device count")
        mesh = make_mesh((jax.device_count() // mp, mp), ("data", "model"))
        mesh_cfg = MeshConfig(data=jax.device_count() // mp, model=mp)
    else:
        mesh = make_mesh((1, 1), ("data", "model"))
        mesh_cfg = MeshConfig(data=1, model=1)

    server = Server(cfg, mesh_cfg, mesh=mesh)
    max_len = args.prompt_len + args.new_tokens + 8
    if cfg.family == "vlm":
        max_len += cfg.image_tokens * cfg.anyres_tiles

    with jax.set_mesh(mesh):
        params = server.shard_params(server.model.init(jax.random.key(args.seed)))
        cache = server.shard_cache(server.model.init_cache(args.batch, max_len))
        batch = {"tokens": jax.random.randint(
            jax.random.key(1), (args.batch, args.prompt_len), 0, cfg.vocab)}
        if cfg.family == "audio":
            batch["frames"] = jax.random.normal(
                jax.random.key(2), (args.batch, cfg.n_frames, cfg.d_model)
            ).astype(jnp.dtype(cfg.param_dtype)) * 0.1
        if cfg.family == "vlm":
            n_img = cfg.image_tokens * cfg.anyres_tiles
            batch["image_embeds"] = jax.random.normal(
                jax.random.key(2), (args.batch, n_img, cfg.d_model)
            ).astype(jnp.dtype(cfg.param_dtype)) * 0.1

        prefill = server.jit_prefill(
            jax.eval_shape(lambda: params),
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch),
            jax.eval_shape(lambda: cache))
        decode = server.jit_decode(
            jax.eval_shape(lambda: params), jax.eval_shape(lambda: cache))

        t0 = time.time()  # noqa: DL002(prefill/decode throughput timing display)
        logits, cache = prefill(params, batch, cache)
        logits = jax.block_until_ready(logits)
        t_prefill = time.time() - t0  # noqa: DL002(prefill/decode throughput timing display)

        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        generated = [tok]
        t0 = time.time()  # noqa: DL002(prefill/decode throughput timing display)
        for _ in range(args.new_tokens - 1):
            logits, cache = decode(params, tok, cache)
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            generated.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0  # noqa: DL002(prefill/decode throughput timing display)

    toks = jnp.concatenate(generated, axis=1)
    tps = args.batch * (args.new_tokens - 1) / max(t_decode, 1e-9)
    print(f"[serve] arch={cfg.name} devices={jax.device_count()} "
          f"batch={args.batch} "
          f"prefill({args.prompt_len} toks)={t_prefill:.3f}s "
          f"decode={t_decode:.3f}s ({tps:.1f} tok/s)")
    print(f"[serve] sample output ids: {toks[0, :12].tolist()}")


if __name__ == "__main__":
    main()
