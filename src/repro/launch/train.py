"""Training driver.

Two modes:

* ``--mode sim`` — the paper's deployment form: a discrete-event WAN
  session running MoDeST / FedAvg / D-SGD over n nodes (Figs. 3–6).

      PYTHONPATH=src python -m repro.launch.train --mode sim --algo modest \\
          --task cnn --nodes 50 --duration 300

* ``--mode mesh`` — the datacenter form: the pjit'd sample-parallel round
  step on a device mesh, with the MoDeST protocol (hash sampling + failure
  masks) running host-side. Pass ``--devices N`` to fake an N-device mesh
  on CPU (must be the first thing the process does, handled below).

      PYTHONPATH=src python -m repro.launch.train --mode mesh --devices 8 \\
          --arch tinyllama-1.1b --rounds 5 --sample-frac 0.5
"""

import os
import sys

if "--devices" in sys.argv:                      # before any jax import
    _n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={_n}"
                               ).strip()

import argparse
import time

import numpy as np


def run_sim(args) -> None:
    import jax

    from repro.config import ModestConfig, TrainConfig
    from repro.data import make_classification_task, make_lm_task, make_mf_task
    from repro.models.tasks import cnn_task, lm_task, mf_task
    from repro.sim.runner import DSGDSession, ModestSession, fedavg_session
    from repro.utils.logging import CSVLogger

    if args.task == "cnn":
        data = make_classification_task(args.nodes, iid=args.iid, seed=args.seed)
        task = cnn_task()
    elif args.task == "mf":
        data = make_mf_task(args.nodes, n_items=500, seed=args.seed)
        task = mf_task(mf_users=args.nodes, mf_items=500)
    else:
        data = make_lm_task(args.nodes, iid=args.iid, seed=args.seed)
        task = lm_task(args.arch)

    mcfg = ModestConfig(n_nodes=args.nodes, sample_size=args.sample_size,
                        n_aggregators=args.aggregators,
                        success_fraction=args.sf, ping_timeout=args.timeout)
    tcfg = TrainConfig(batch_size=args.batch_size, seed=args.seed)

    if args.algo == "dsgd":
        session = DSGDSession(n_nodes=args.nodes, tcfg=tcfg, task=task,
                              data=data, seed=args.seed,
                              eval_every_rounds=args.eval_every)
    elif args.algo == "fedavg":
        session = fedavg_session(n_nodes=args.nodes, mcfg=mcfg, tcfg=tcfg,
                                 task=task, data=data, seed=args.seed,
                                 eval_every_rounds=args.eval_every)
    else:
        session = ModestSession(n_nodes=args.nodes, mcfg=mcfg, tcfg=tcfg,
                                task=task, data=data, seed=args.seed,
                                eval_every_rounds=args.eval_every)

    if args.ckpt and args.algo in ("modest", "fedavg"):
        # persist the latest aggregated model periodically (and on exit)
        from repro import checkpoint

        orig_hook = session._on_aggregate
        state = {"last": 0}

        def hook(k, params, node):
            orig_hook(k, params, node)
            if params is not None and k - state["last"] >= args.ckpt_every:
                state["last"] = k
                checkpoint.save(args.ckpt, params,
                                meta={"round": k, "algo": args.algo,
                                      "task": args.task})

        session._on_aggregate = hook
        for node in session.nodes.values():
            node.on_aggregate = hook

    res = session.run(args.duration)
    log = CSVLogger(args.out)
    for h in res.history:
        log.log(algo=args.algo, **h)
    print(f"[train:sim] algo={args.algo} rounds={res.rounds_completed} "
          f"total={res.usage['total_bytes'] / 1e9:.2f}GB "
          f"min={res.usage['min_node_bytes'] / 1e6:.1f}MB "
          f"max={res.usage['max_node_bytes'] / 1e6:.1f}MB "
          f"overhead={res.overhead_fraction:.3%} final={res.final_metrics}")


def run_mesh(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.config import MeshConfig, ModestConfig, TrainConfig
    from repro.core.distributed import DistributedTrainer
    from repro.core.hashing import select_sample
    from repro.data import make_lm_task

    n_dev = jax.device_count()
    model_par = args.model_parallel
    data_par = n_dev // model_par
    mesh_cfg = MeshConfig(multi_pod=False, data=data_par, model=model_par)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((data_par, model_par), ("data", "model"))

    cfg = configs.get_config(args.arch)
    if not args.full_size:
        cfg = configs.reduced(cfg)
    tcfg = TrainConfig(optimizer="sgd", lr=args.lr, batch_size=args.batch_size,
                       seed=args.seed)
    trainer = DistributedTrainer(cfg, tcfg, mesh_cfg, strategy=args.algo,
                                 mesh=mesh, donate=False)
    P = trainer.policy.n_participants

    # Host-side MoDeST protocol: population of client ids; each round the
    # hash sampler picks P clients; crash/straggler masks map to weights.
    population = [f"client-{i}" for i in range(args.nodes)]
    data = make_lm_task(args.nodes, seq_len=args.seq_len + 1,
                        vocab=cfg.vocab, seed=args.seed)
    rng = np.random.default_rng(args.seed)

    with jax.set_mesh(mesh):
        state = trainer.init_state(args.seed)
        step = trainer.jit_train_step(
            batch_template=jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    (P, args.local_steps, args.batch_size, args.seq_len),
                    jnp.int32),
                {"tokens": 0, "labels": 0}))
        for r in range(1, args.rounds + 1):
            sample_ids = select_sample(population, r, P)
            idxs = [population.index(s) for s in sample_ids]
            xs, ys = [], []
            for e in range(args.local_steps):
                x, y = data.pack_sample(idxs, args.batch_size, seed=r * 31 + e)
                xs.append(x[:, :, :args.seq_len])
                ys.append(y[:, :, :args.seq_len])
            batch = {"tokens": jnp.asarray(np.stack(xs, axis=1)),
                     "labels": jnp.asarray(np.stack(ys, axis=1))}
            # sf semantics: drop slots that "failed" this round
            weights = (rng.random(P) >= args.failure_rate).astype(np.float32)
            if weights.sum() == 0:
                weights[0] = 1.0
            t0 = time.time()  # noqa: DL002(per-round step timing display)
            state, metrics = step(state, batch, jnp.asarray(weights))
            loss = float(metrics["loss"])
            print(f"[train:mesh] round={r} sample={sample_ids[:4]}... "
                  f"active={int(weights.sum())}/{P} loss={loss:.4f} "
                  f"({time.time() - t0:.2f}s)")  # noqa: DL002(per-round step timing display)
    print("[train:mesh] done")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="sim", choices=["sim", "mesh"])
    ap.add_argument("--algo", default="modest",
                    choices=["modest", "fedavg", "dsgd", "local"])
    ap.add_argument("--task", default="cnn", choices=["cnn", "mf", "lm"])
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--nodes", type=int, default=50)
    ap.add_argument("--sample-size", type=int, default=10)
    ap.add_argument("--aggregators", type=int, default=2)
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--timeout", type=float, default=1.0)
    ap.add_argument("--batch-size", type=int, default=20)
    ap.add_argument("--duration", type=float, default=300.0)
    ap.add_argument("--eval-every", type=int, default=10)
    ap.add_argument("--iid", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint path for the aggregated global model")
    ap.add_argument("--ckpt-every", type=int, default=20)
    # mesh mode
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--model-parallel", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--failure-rate", type=float, default=0.0)
    ap.add_argument("--full-size", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.mode == "sim":
        run_sim(args)
    else:
        run_mesh(args)


if __name__ == "__main__":
    main()
