"""Mesh-form integration: the pjit'd sample-parallel round step on a small
faked device mesh (subprocess — the device count must be set before jax
initializes, and the main test process stays single-device)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_in_subprocess(body: str) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro import configs
        from repro.config import MeshConfig, TrainConfig
        from repro.core.distributed import DistributedTrainer, Server
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        mesh_cfg = MeshConfig(data=4, model=2)
    """) + textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=420,
                          env={**os.environ, "PYTHONPATH": SRC})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_modest_round_step_trains_and_masks():
    out = run_in_subprocess("""
        cfg = configs.reduced(configs.get_config("tinyllama-1.1b"))
        trainer = DistributedTrainer(cfg, TrainConfig(optimizer="sgd", lr=0.1),
                                     mesh_cfg, strategy="modest", mesh=mesh,
                                     donate=False)
        P = trainer.policy.n_participants
        assert P == 4
        with jax.set_mesh(mesh):
            state = trainer.init_state(0)
            B, S = 2, 32
            tmpl = {k: jax.ShapeDtypeStruct((P, 1, B, S), jnp.int32)
                    for k in ("tokens", "labels")}
            step = trainer.jit_train_step(batch_template=tmpl)
            toks = np.random.default_rng(1).integers(
                0, cfg.vocab, size=(P, 1, B, S)).astype(np.int32)
            batch = {"tokens": toks, "labels": toks}   # uncommitted: jit places
            losses = []
            for r in range(4):
                w = np.asarray([1., 1., 0., 1.], np.float32)  # slot 2 failed (sf)
                state, m = step(state, batch, w)
                losses.append(float(m["loss"]))
            # replicas equal after aggregation broadcast
            p0 = jax.tree.leaves(state.params)[0]
            diff = float(jnp.max(jnp.abs(p0[0].astype(jnp.float32)
                                         - p0[1].astype(jnp.float32))))
            print("LOSSES", losses)
            print("REPLDIFF", diff)
        assert losses[-1] < losses[0], losses
        assert diff < 1e-5
        print("OK")
    """)
    assert "OK" in out


def test_dsgd_step_keeps_replica_divergence():
    out = run_in_subprocess("""
        cfg = configs.reduced(configs.get_config("tinyllama-1.1b"))
        trainer = DistributedTrainer(cfg, TrainConfig(optimizer="sgd", lr=0.1),
                                     mesh_cfg, strategy="dsgd", mesh=mesh,
                                     donate=False)
        P = trainer.policy.n_participants
        with jax.set_mesh(mesh):
            state = trainer.init_state(0)
            B, S = 2, 32
            tmpl = {k: jax.ShapeDtypeStruct((P, 1, B, S), jnp.int32)
                    for k in ("tokens", "labels")}
            step = trainer.jit_train_step(batch_template=tmpl)
            # different data per slot => replicas diverge; dsgd only mixes
            # pairwise, so divergence persists (residual variance, paper §2)
            toks = np.random.default_rng(1).integers(
                0, cfg.vocab, size=(P, 1, B, S)).astype(np.int32)
            batch = {"tokens": toks, "labels": toks}
            state, _ = step(state, batch, np.ones(P, np.float32))
            p0 = jax.tree.leaves(state.params)[0]
            diff = float(jnp.max(jnp.abs(p0[0].astype(jnp.float32)
                                         - p0[1].astype(jnp.float32))))
            print("DIFF", diff)
        assert diff > 1e-6, diff
        print("OK")
    """)
    assert "OK" in out


def test_serve_sharded_prefill_decode():
    out = run_in_subprocess("""
        cfg = configs.reduced(configs.get_config("gemma2-27b"))
        server = Server(cfg, mesh_cfg, mesh=mesh)
        with jax.set_mesh(mesh):
            params = server.shard_params(server.model.init(jax.random.key(0)))
            cache = server.shard_cache(server.model.init_cache(4, 24))
            batch = {"tokens": np.random.default_rng(1).integers(
                0, cfg.vocab, size=(4, 16)).astype(np.int32)}
            prefill = server.jit_prefill(
                jax.eval_shape(lambda: params),
                jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                             batch),
                jax.eval_shape(lambda: cache))
            logits, cache = prefill(params, batch, cache)
            decode = server.jit_decode(jax.eval_shape(lambda: params),
                                       jax.eval_shape(lambda: cache))
            tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
            logits, cache = decode(params, tok, cache)
            assert logits.shape == (4, 1, cfg.vocab)
            assert bool(jnp.all(jnp.isfinite(logits)))
        print("OK")
    """)
    assert "OK" in out
