"""The pjit'd sample-parallel round step — MoDeST on a TPU mesh.

``make_train_step`` builds one jitted function computing a full MoDeST
round in the mesh form (DESIGN.md §3):

1. every participant slot runs ``E`` local SGD steps on its own replica
   (vmap over the participant axis ⇒ sharded over ``data``/``pod``);
2. the round's aggregation is the strategy's masked collective
   (all-reduce for modest/fedavg, collective-permute for dsgd).

``weights`` is the host-side protocol's output: which slots count this
round (sampling mask, ``sf`` failures, stragglers). The step is
protocol-agnostic — the same compiled artifact serves MoDeST, FedAvg and
D-SGD; only the mask/strategy differ, which is what makes the collective
cost comparison (paper Table 4) visible in HLO.

``make_serve_fns`` builds the jitted prefill / decode_step for the
inference shapes.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import optim
from repro.config import MeshConfig, ModelConfig, ShapeConfig, TrainConfig
from repro.core.strategy import Strategy, build_strategy
from repro.models import Model, build
from repro.sharding import ShardingPolicy, input_specs


class TrainState(NamedTuple):
    params: Any          # (P, ...) stacked replicas
    opt_state: Any       # (P, ...) per-participant optimizer state
    server_state: Any    # aggregator-side optimizer state (FedYogi etc.)
    round: jnp.ndarray


def _stack_template(tree, P):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((P,) + tuple(l.shape), l.dtype), tree)


class DistributedTrainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig,
                 mesh_cfg: MeshConfig, *, strategy: str = "modest",
                 mesh=None, donate: bool = True):
        self.cfg, self.tcfg, self.mesh_cfg = cfg, tcfg, mesh_cfg
        self.model: Model = build(cfg)
        self.policy = ShardingPolicy(cfg, mesh_cfg)
        self.strategy: Strategy = build_strategy(strategy, tcfg)
        self.opt = optim.build(tcfg)
        self.mesh = mesh
        self._donate = donate

    # ------------------------------------------------------------------ state

    def abstract_state(self) -> TrainState:
        P = self.policy.n_participants
        params = jax.eval_shape(self.model.init, jax.random.key(0))
        opt_state = jax.eval_shape(self.opt.init, params)
        params_P = _stack_template(params, P)
        opt_P = _stack_template(opt_state, P)
        server = jax.eval_shape(self.strategy.init_state, params_P)
        return TrainState(params_P, opt_P, server, jnp.zeros((), jnp.int32))

    def init_state(self, seed: int = 0) -> TrainState:
        P = self.policy.n_participants
        params = self.model.init(jax.random.key(seed))
        params_P = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (P,) + x.shape), params)
        opt_state = self.opt.init(params)
        opt_P = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (P,) + x.shape), opt_state)
        server = self.strategy.init_state(params_P)
        state = TrainState(params_P, opt_P, server, jnp.zeros((), jnp.int32))
        if self.mesh is not None:
            state = self.shard_state(state)
        return state

    def shard_state(self, state: TrainState) -> TrainState:
        """Place a host-initialized state onto the mesh per the policy."""
        from jax.sharding import NamedSharding

        specs = self.state_spec(state)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            state, specs)

    # ------------------------------------------------------------- shardings

    def state_spec(self, state: TrainState):
        # params/opt leaves carry (P, ...); reuse param rules then prepend P.
        from jax.sharding import PartitionSpec as P

        params_spec = self.policy.param_spec(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                         state.params), with_participants=False)
        part = self.policy.part_axis

        def prepend(spec):
            return P(part, *spec)

        params_P_spec = jax.tree.map(prepend, params_spec,
                                     is_leaf=lambda s: isinstance(s, P))
        opt_template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), state.opt_state)
        opt_spec = jax.tree.map(
            prepend,
            self.policy.param_spec(opt_template, with_participants=False),
            is_leaf=lambda s: isinstance(s, P))
        if jax.tree_util.tree_leaves(state.server_state):
            server_spec = self.policy.param_spec(state.server_state,
                                                 with_participants=False)
        else:
            server_spec = jax.tree.map(lambda _: P(), state.server_state)
        return TrainState(params_P_spec, opt_spec, server_spec, P())

    # ------------------------------------------------------------- train step

    def build_train_step(self, *, local_steps: int = 1, hop: int = 1,
                         accumulate: bool = False):
        """``accumulate=False`` — the E axis is MoDeST's sequential local
        SGD steps (one optimizer update per slice; paper-faithful).
        ``accumulate=True`` — the E axis is grad-accumulation microbatching
        of ONE step (correct for the paper's E=1 single local pass when the
        batch must be split for memory; params stay loop-invariant so FSDP
        all-gathers hoist out of the scan — §Perf)."""
        model, opt, strategy = self.model, self.opt, self.strategy

        def per_participant(params, opt_state, batch):
            """batch leaves: (E, B, ...)."""

            if accumulate:
                def one_acc(carry, mb):
                    acc, loss_sum = carry
                    (loss, _m), grads = jax.value_and_grad(
                        model.loss_fn, has_aux=True)(params, mb)
                    acc = jax.tree.map(jnp.add, acc, grads)
                    return (acc, loss_sum + loss), None

                zeros = jax.tree.map(jnp.zeros_like, params)
                (grads, loss_sum), _ = jax.lax.scan(
                    one_acc, (zeros, jnp.zeros(())), batch)
                n = jax.tree.leaves(batch)[0].shape[0]
                grads = jax.tree.map(lambda g: g / n, grads)
                upd, opt_state = opt.update(grads, opt_state, params)
                return optim.apply_updates(params, upd), opt_state, loss_sum / n

            def one_step(carry, mb):
                p, o = carry
                (loss, _m), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(p, mb)
                upd, o = opt.update(grads, o, p)
                return (optim.apply_updates(p, upd), o), loss

            (params, opt_state), losses = jax.lax.scan(
                one_step, (params, opt_state), batch)
            return params, opt_state, losses.mean()

        def train_step(state: TrainState, batch, weights):
            prev = state.params
            params_P, opt_P, losses = jax.vmap(per_participant)(
                state.params, state.opt_state, batch)
            new_P, server = strategy.mix(prev, params_P, weights,
                                         state.server_state, hop)
            metrics = {"loss": losses.mean(),
                       "active": jnp.sum(weights)}
            return TrainState(new_P, opt_P, server, state.round + 1), metrics

        return train_step

    def jit_train_step(self, state_template: Optional[TrainState] = None,
                       batch_template=None, **kw):
        state_template = state_template or self.abstract_state()
        specs = self.state_spec(state_template)
        from jax.sharding import PartitionSpec as P

        batch_spec = (self.policy.batch_spec(batch_template,
                                             with_participants=True)
                      if batch_template is not None else None)
        step = self.build_train_step(**kw)
        return jax.jit(
            step,
            in_shardings=(specs, batch_spec, self.policy.weights_spec()),
            out_shardings=(specs, None),
            donate_argnums=(0,) if self._donate else ())


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


class Server:
    """Batched serving: jitted prefill + single-token decode."""

    def __init__(self, cfg: ModelConfig, mesh_cfg: MeshConfig, *, mesh=None,
                 shard_seq: bool = False):
        self.cfg = cfg
        self.model = build(cfg)
        self.policy = ShardingPolicy(cfg, mesh_cfg)
        self.mesh = mesh
        self.shard_seq = shard_seq

    def abstract_cache(self, batch_size: int, max_len: int):
        return jax.eval_shape(lambda: self.model.init_cache(batch_size, max_len))

    def specs(self, params_t, cache_t):
        pspec = self.policy.param_spec(params_t, with_participants=False)
        cspec = self.policy.cache_spec(cache_t, shard_seq=self.shard_seq)
        return pspec, cspec

    def shard_params(self, params):
        """Place host-initialized params onto the mesh per the policy."""
        from jax.sharding import NamedSharding

        spec = self.policy.param_spec(params, with_participants=False)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            params, spec)

    def shard_cache(self, cache):
        from jax.sharding import NamedSharding

        spec = self.policy.cache_spec(cache, shard_seq=self.shard_seq)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            cache, spec)

    def jit_prefill(self, params_t, batch_t, cache_t):
        pspec, cspec = self.specs(params_t, cache_t)
        bspec = self.policy.batch_spec(batch_t, with_participants=False,
                                       shard_seq=self.shard_seq)
        return jax.jit(self.model.prefill,
                       in_shardings=(pspec, bspec, cspec),
                       out_shardings=(None, cspec))

    def jit_decode(self, params_t, cache_t, batch_size: Optional[int] = None):
        from jax.sharding import PartitionSpec as P

        pspec, cspec = self.specs(params_t, cache_t)
        b = batch_size or jax.tree_util.tree_leaves(cache_t)[0].shape[1]
        spec = self.policy._fix_divisibility(
            (None if self.shard_seq else "data", None), (b, 1))
        tok_spec = P(*spec)
        return jax.jit(self.model.decode_step,
                       in_shardings=(pspec, tok_spec, cspec),
                       out_shardings=(None, cspec),
                       donate_argnums=(2,))
