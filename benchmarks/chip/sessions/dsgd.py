"""A D-SGD session (``repro.sim.runner.DSGDSession``: the session's own
one-peer exponential graph, Ying et al. 2021) on the engine the harness
gives it. Every node trains ``local_epochs`` over its shard each round
and averages with its one in-neighbour of the round."""

from __future__ import annotations


def build(*, task, data, profile, traffic: dict, tcfg, seed: int, engine):
    from harness import given_engine
    from repro.sim.runner import DSGDSession

    if traffic["local_epochs"] != 1:
        raise ValueError("DSGDSession trains one local epoch a round")
    with given_engine(engine):
        return DSGDSession(n_nodes=traffic["nodes"], tcfg=tcfg, task=task,
                           data=data, seed=seed, profile=profile,
                           churn_from_profile=traffic["churn"],
                           eval_every_rounds=traffic["eval_every_rounds"])
