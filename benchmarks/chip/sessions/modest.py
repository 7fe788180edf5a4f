"""A MoDeST/Plexus session (``repro.sim.runner.ModestSession``) on the
engine the harness gives it."""

from __future__ import annotations


def build(*, task, data, profile, traffic: dict, tcfg, seed: int, engine):
    from harness import given_engine
    from repro.config import ModestConfig
    from repro.sim.runner import ModestSession

    n = traffic["nodes"]
    mcfg = ModestConfig(n_nodes=n, sample_size=traffic["sample_size"],
                        n_aggregators=traffic["aggregators"],
                        success_fraction=traffic["success_fraction"],
                        ping_timeout=traffic["ping_timeout"],
                        local_steps=traffic["local_epochs"], seed=seed)
    with given_engine(engine):
        return ModestSession(n_nodes=n, mcfg=mcfg, tcfg=tcfg, task=task,
                             data=data, seed=seed, profile=profile,
                             churn_from_profile=traffic["churn"],
                             eval_every_rounds=traffic["eval_every_rounds"])
