"""What every plain reference shares: the order of local batches and the
optimizers of the paper's tasks. Imports nothing of the program."""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np


def batches(x, y, batch_size: int, *, seed: int, epochs: int,
            rows: Optional[int] = None):
    """``(x, y, mask)`` per local step: each epoch visits the samples in the
    order of ``default_rng(seed).permutation`` (one generator over all
    epochs), ``batch_size`` at a time. A short last batch is padded to
    ``batch_size`` with rows of mask 0, which carry no weight. ``rows``
    leaves all but the first ``rows`` rows of every batch out (a planted
    fault for the checks' own tests)."""
    rng = np.random.default_rng(seed)
    for _ in range(epochs):
        order = rng.permutation(len(x))
        for lo in range(0, len(order), batch_size):
            sel = order[lo:lo + batch_size]
            mask = np.zeros(batch_size, np.float32)
            mask[:len(sel)] = 1.0
            if rows is not None:
                mask[rows:] = 0.0
            pad = np.concatenate([sel, np.repeat(sel[:1],
                                                 batch_size - len(sel))])
            yield x[pad], y[pad], mask


def optimizer(train: dict, dtype):
    """``update(params, grads, state) -> (params, state)`` for plain SGD or
    heavy-ball momentum (``m = beta·m + g``; ``p -= lr·m``), state started
    at zero for every local training."""
    lr = jnp.asarray(train["lr"], dtype)
    if train["optimizer"] == "sgd":
        def update(p, g, state):
            return {k: p[k] - lr * g[k] for k in p}, state
    elif train["optimizer"] == "momentum":
        beta = jnp.asarray(train["momentum"], dtype)

        def update(p, g, state):
            m = {k: beta * state[k] + g[k] for k in p}
            return {k: p[k] - lr * m[k] for k in p}, m
    else:
        raise ValueError(f"no reference optimizer {train['optimizer']!r}")
    return update
