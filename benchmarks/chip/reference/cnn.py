"""Plain reference of the paper's LeNet-style CNN (MoDeST Table 3).

Two 5x5 "same" convolutions with ReLU, each followed by a 2x2 max pool,
then dense layers with ReLU and a linear output; softmax cross entropy.
Written from the architecture alone in ``jax.numpy``: it imports nothing
of the program and batches nothing.

In float32 every contraction (the convolutions, the dense layers and their
gradients) multiplies operands rounded to ``model_cfg["matmul_operands"]``
exactly and sums in float32: with ``"bfloat16"`` that is what a TPU's
matrix unit does with float32 operands at the default precision, which is
the precision the configuration states; with ``"float32"`` it is exact
float32. Any other ``dtype`` (the control) casts the parameters, the
inputs and the optimizer state to it and computes there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from refcommon import batches, optimizer


def _conv(h, w, precision):
    return jax.lax.conv_general_dilated(
        h, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision)


def _dot(h, w, precision):
    return jnp.dot(h, w, precision=precision)


@functools.lru_cache(maxsize=None)
def _contraction(kind: str, operands: str, dtype_name: str):
    """``f(a, b)`` for ``kind`` ("conv" or "dot"). In float32 with
    ``operands`` narrower than float32, the forward and both gradient
    contractions take operands rounded to ``operands`` and compute on them
    exactly; the rounding passes gradients straight through."""
    op = {"conv": _conv, "dot": _dot}[kind]
    hi = jax.lax.Precision.HIGHEST
    if jnp.dtype(dtype_name) != jnp.float32:
        return functools.partial(op, precision=jax.lax.Precision.DEFAULT)
    exact = functools.partial(op, precision=hi)
    if jnp.dtype(operands) == jnp.float32:
        return exact

    def rnd(v):
        return v.astype(operands).astype(jnp.float32)

    @jax.custom_vjp
    def f(a, b):
        return exact(rnd(a), rnd(b))

    def fwd(a, b):
        ra, rb = rnd(a), rnd(b)
        return exact(ra, rb), (ra, rb)

    def bwd(res, g):
        return jax.vjp(exact, *res)[1](rnd(g))

    f.defvjp(fwd, bwd)
    return f


def logits(p, x, dtype, operands="float32"):
    name = jnp.dtype(dtype).name
    conv_op = _contraction("conv", operands, name)
    dot_op = _contraction("dot", operands, name)

    def conv(h, w, b):
        return jax.nn.relu(conv_op(h, w) + b)

    def pool(h):
        n, hh, ww, c = h.shape
        return h.reshape(n, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))

    h = pool(conv(x, p["conv1"], p["b1"]))
    h = pool(conv(h, p["conv2"], p["b2"]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(dot_op(h, p["fc1"]))
    h = jax.nn.relu(dot_op(h, p["fc2"]))
    return dot_op(h, p["out"])


def _xent(z, y):
    return jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
        z, y[:, None], axis=-1)[:, 0]


def loss(p, x, y, mask, dtype, operands):
    """Mean cross entropy over the rows whose mask is 1."""
    nll = _xent(logits(p, x, dtype, operands), y)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


@functools.lru_cache(maxsize=None)
def _step(train_key, dtype_name, operands):
    dtype = jnp.dtype(dtype_name)
    train = dict(train_key)
    opt_update = optimizer(train, dtype)

    @jax.jit
    def step(p, state, x, y, mask):
        value, g = jax.value_and_grad(loss)(p, x, y, mask, dtype,
                                                 operands)
        p, state = opt_update(p, g, state)
        return p, state, value

    return step


def train(params: dict, x, y, *, batch_size: int, epochs: int, seed: int,
          train_cfg: dict, model_cfg: dict, dtype="float32",
          rows=None) -> dict:
    """The parameters after ``epochs`` passes over ``(x, y)`` in the
    seeded order, one optimizer step per batch of ``batch_size``."""
    dt = jnp.dtype(dtype)
    step = _step(tuple(sorted(train_cfg.items())), dt.name,
                 model_cfg.get("matmul_operands", "float32"))
    p = {k: jnp.asarray(v, dt) for k, v in params.items()}
    state = jax.tree.map(jnp.zeros_like, p)
    for xb, yb, mb in batches(x, y, batch_size, seed=seed, epochs=epochs,
                              rows=rows):
        p, state, _ = step(p, state, jnp.asarray(xb, dt),
                           jnp.asarray(yb, jnp.int32), jnp.asarray(mb, dt))
    return {k: np.asarray(v, np.float32) for k, v in p.items()}


@functools.lru_cache(maxsize=None)
def _eval_block(dtype_name, operands):
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def block(p, x, y):
        z = logits(p, x, dtype, operands)
        nll = _xent(z.astype(jnp.float32) if dtype == jnp.float32 else z, y)
        hit = (jnp.argmax(z, -1) == y)
        return jnp.sum(nll.astype(jnp.float32)), jnp.sum(hit)

    return block


def evaluate(params: dict, x, y, *, model_cfg: dict, dtype="float32",
             block: int = 500) -> dict:
    """Mean cross entropy and accuracy over every test sample."""
    dt = jnp.dtype(dtype)
    fn = _eval_block(dt.name, model_cfg.get("matmul_operands", "float32"))
    p = {k: jnp.asarray(v, dt) for k, v in params.items()}
    total, hits = 0.0, 0
    for lo in range(0, len(x), block):
        xb = x[lo:lo + block]
        s, h = fn(p, jnp.asarray(xb, dt), jnp.asarray(y[lo:lo + block],
                                                      jnp.int32))
        total += float(s)
        hits += int(h)
    return {"loss": total / len(x), "accuracy": hits / len(x)}
