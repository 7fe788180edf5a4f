"""Jit'd public wrappers: pad to tile size, run the Pallas kernel, unpad.

``interpret`` defaults to True on CPU backends (this container) and False
on TPU, so the same call sites work in tests and production.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.flat import FlatModel, FlatSpec, as_buffer
from repro.kernels.aggregate import TILE, aggregate_tiles
from repro.kernels.fused import (SUBTILE, aggregate_flat_onepass, bits,
                                 aggregate_flat_onepass_sharded,
                                 aggregate_quantize_flat,
                                 aggregate_quantize_flat_sharded,
                                 unmask_aggregate_flat,
                                 unmask_aggregate_flat_sharded,
                                 unmask_aggregate_quantize_flat,
                                 unmask_aggregate_quantize_flat_sharded)
from repro.kernels.quantize import dequantize_tiles, quantize_tiles
from repro.utils.pytree import check_aggregation_weights as _check_weights


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to_tile(x_flat):
    n = x_flat.shape[-1]
    pad = (-n) % TILE
    if pad:
        x_flat = jnp.pad(x_flat, [(0, 0)] * (x_flat.ndim - 1) + [(0, pad)])
    return x_flat, n


def aggregate_flat(x, w, *, interpret=None):
    """x: (P, N) stacked flattened models; w: (P,). Weighted mean (N,)."""
    interpret = _default_interpret() if interpret is None else interpret
    _check_weights(w)
    xp, n = _pad_to_tile(x)
    return aggregate_tiles(xp, w, interpret=interpret)[:n]


def aggregate_pytree(models, weights, *, interpret=None):
    """MoDeST aggregation over a list of model pytrees via the kernel.

    Per-leaf path: one ``pallas_call`` per pytree leaf. Kept as the
    reference kernel path and for the engine's speedup benchmarks; the
    hot loop uses :func:`aggregate_flatmodel` (one call per model).
    """
    interpret = _default_interpret() if interpret is None else interpret
    _check_weights(weights)
    w = jnp.asarray(weights, jnp.float32)

    def leaf(*xs):
        # Integer leaves (optimizer step counters, token counts) must not
        # be truncated on the way back to int: 6.999999 is 7, not 6. The
        # kernel emits x.dtype, so ints go through it as fp32 and are
        # rounded to nearest at the end.
        dt = jnp.dtype(xs[0].dtype)
        is_int = jnp.issubdtype(dt, jnp.integer)
        flat = [jnp.ravel(x).astype(jnp.float32) if is_int else jnp.ravel(x)
                for x in xs]
        out = aggregate_flat(jnp.stack(flat), w, interpret=interpret)
        out = out.reshape(xs[0].shape)
        if is_int:
            out = jnp.round(out)
        return out.astype(dt)

    return jax.tree.map(leaf, *models)


# ---------------------------------------------------------------------------
# Whole-model one-pass aggregation (FlatModel engine)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _jnp_onepass(spec_n: int, has_int: bool):
    def agg(x, w, int_mask):
        total = jnp.sum(w)
        mean = jnp.tensordot(w, x, axes=(0, 0)) / total
        if has_int:
            mean = jnp.where(int_mask, jnp.round(mean), mean)
        return mean

    return jax.jit(agg)


@functools.lru_cache(maxsize=64)
def _jnp_onepass_quant(spec_n: int, has_int: bool):
    """Fused aggregate→quantize, XLA-fused single jit (CPU default).

    Same contraction + per-SUBTILE quantization as the Pallas kernel;
    codes/scales are bit-identical to ``quantize_ref`` of the padded mean.
    """
    from repro.kernels.fused import SUBTILE

    pad = (-spec_n) % SUBTILE

    def agg(x, w, int_mask):
        total = jnp.sum(w)
        mean = jnp.tensordot(w, x, axes=(0, 0)) / total
        if has_int:
            mean = jnp.where(int_mask, jnp.round(mean), mean)
        t = jnp.pad(mean, (0, pad)).reshape(-1, SUBTILE)
        scales = jnp.maximum(jnp.max(jnp.abs(t), axis=1), 1e-12) / 127.0
        q = jnp.clip(jnp.round(t / scales[:, None]), -127, 127)
        return mean, q.reshape(-1)[:spec_n].astype(jnp.int8), scales

    return jax.jit(agg)


def aggregate_flatmodel(models, weights=None, *, spec=None, quantize=False,
                        interpret=None, use_kernel=None, shardings=None):
    """Whole-model one-pass aggregation over FlatModels (or pytrees).

    ``models``: list of :class:`~repro.engine.flat.FlatModel` and/or
    pytrees (mixed is fine — trees are packed against ``spec``, derived
    from the first model when omitted). Returns a FlatModel; with
    ``quantize=True`` returns ``(FlatModel, codes int8 (n,), scales)``
    from the fused aggregate→quantize kernel — no extra HBM round trip.

    ``use_kernel``: force the Pallas path (True) or the jnp one-pass
    contraction (False). Default: Pallas on TPU, jnp elsewhere — on CPU
    the interpret-mode kernel exists for validation, not speed. Both paths
    are a single fused pass over the ``(P, N)`` stack either way.

    ``shardings``: a :class:`repro.sharding.FlatShardings` (from
    ``spec.sharding(mesh)``) shards the parameter axis over the mesh's
    ``model`` axis and aggregates per shard; the result mean and int8
    codes are bit-identical to the single-device path (docs/SHARDING.md).
    Ignored on a 1-shard mesh.
    """
    if weights is None:
        weights = [1.0] * len(models)
    _check_weights(weights)
    if spec is None:
        first = models[0]
        spec = first.spec if isinstance(first, FlatModel) else \
            FlatSpec.from_tree(first)
    x = jnp.stack([as_buffer(m, spec) for m in models])
    w = jnp.asarray(weights, jnp.float32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    interpret = _default_interpret() if interpret is None else interpret
    int_mask = jnp.asarray(spec.int_mask) if spec.has_int else None
    if shardings is not None and shardings.n_shards > 1:
        mask = (int_mask.astype(jnp.float32) if int_mask is not None
                else None)
        if quantize:
            mean, codes, scales = aggregate_quantize_flat_sharded(
                x, w, mask, mesh=shardings.mesh,
                model_axis=shardings.model_axis,
                use_kernel=use_kernel, interpret=interpret)
            return FlatModel(mean, spec), codes, scales
        mean = aggregate_flat_onepass_sharded(
            x, w, mask, mesh=shardings.mesh,
            model_axis=shardings.model_axis,
            use_kernel=use_kernel, interpret=interpret)
        return FlatModel(mean, spec)
    if quantize:
        if use_kernel:
            mask = (int_mask.astype(jnp.float32) if int_mask is not None
                    else jnp.zeros((spec.n,), jnp.float32))
            mean, codes, scales = aggregate_quantize_flat(
                x, w, mask, interpret=interpret)
        else:
            mask = int_mask if int_mask is not None \
                else jnp.zeros((), jnp.bool_)
            mean, codes, scales = _jnp_onepass_quant(
                spec.n, spec.has_int)(x, w, mask)
        return FlatModel(mean, spec), codes, scales
    if use_kernel:
        mask = (int_mask.astype(jnp.float32) if int_mask is not None
                else jnp.zeros((spec.n,), jnp.float32))
        mean = aggregate_flat_onepass(x, w, mask, interpret=interpret)
    else:
        mask = int_mask if int_mask is not None else jnp.zeros((), jnp.bool_)
        mean = _jnp_onepass(spec.n, spec.has_int)(x, w, mask)
    return FlatModel(mean, spec)


def masked_aggregate_flatmodel(models, weights=None, *, seeds, signs,
                               spec=None, quantize=False, interpret=None,
                               use_kernel=None, shardings=None):
    """Secure-aggregation twin of :func:`aggregate_flatmodel`.

    ``models`` are FlatModels whose buffers hold *sealed* bit patterns
    (``repro.secureagg.masking``); ``seeds``/``signs`` are the per-row
    ``(P, R)`` mask-derivation matrices from
    ``PairwiseMasker.unmask_matrices``. The kernels regenerate each
    row's mask from its seeds, remove it exactly in the uint32 ring and
    run the identical aggregate(→quantize) math — mean/codes/scales are
    bit-identical to :func:`aggregate_flatmodel` on the unsealed rows,
    on every dispatch path (kernel, jnp, sharded).
    """
    if weights is None:
        weights = [1.0] * len(models)
    _check_weights(weights)
    if spec is None:
        spec = models[0].spec
    y = jnp.stack([bits(as_buffer(m, spec)) for m in models])
    w = jnp.asarray(weights, jnp.float32)
    seeds = jnp.asarray(seeds, jnp.uint32)
    signs = jnp.asarray(signs, jnp.int32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    interpret = _default_interpret() if interpret is None else interpret
    int_mask = jnp.asarray(spec.int_mask) if spec.has_int else None
    if shardings is not None and shardings.n_shards > 1:
        mask = (int_mask.astype(jnp.float32) if int_mask is not None
                else None)
        if quantize:
            mean, codes, scales = unmask_aggregate_quantize_flat_sharded(
                y, w, mask, seeds=seeds, signs=signs, mesh=shardings.mesh,
                model_axis=shardings.model_axis,
                use_kernel=use_kernel, interpret=interpret)
            return FlatModel(mean, spec), codes, scales
        mean = unmask_aggregate_flat_sharded(
            y, w, mask, seeds=seeds, signs=signs, mesh=shardings.mesh,
            model_axis=shardings.model_axis,
            use_kernel=use_kernel, interpret=interpret)
        return FlatModel(mean, spec)
    if use_kernel:
        mask = (int_mask.astype(jnp.float32) if int_mask is not None
                else jnp.zeros((spec.n,), jnp.float32))
        if quantize:
            mean, codes, scales = unmask_aggregate_quantize_flat(
                y, w, mask, seeds=seeds, signs=signs, interpret=interpret)
            return FlatModel(mean, spec), codes, scales
        mean = unmask_aggregate_flat(y, w, mask, seeds=seeds, signs=signs,
                                     interpret=interpret)
        return FlatModel(mean, spec)
    # jnp path: exact ring unmask, then the SAME _jnp_onepass* contraction
    # the plain path runs — bit-identity by construction.
    x = _jnp_unmask_stack(spec.n)(y, seeds, signs)
    mask = int_mask if int_mask is not None else jnp.zeros((), jnp.bool_)
    if quantize:
        mean, codes, scales = _jnp_onepass_quant(spec.n, spec.has_int)(
            x, w, mask)
        return FlatModel(mean, spec), codes, scales
    return FlatModel(_jnp_onepass(spec.n, spec.has_int)(x, w, mask), spec)


@functools.lru_cache(maxsize=64)
def _jnp_unmask_stack(spec_n: int):
    from repro.kernels.fused import _unmask_bits

    def unmask(y, seeds, signs):
        lanes = jnp.arange(spec_n, dtype=jnp.uint32)[None, :]
        return _unmask_bits(y, seeds, signs, lanes, spec_n)

    return jax.jit(unmask)


def quantize_flat(x, *, interpret=None):
    """x: (N,) -> (int8 codes (N,), per-tile scales); N padded internally."""
    interpret = _default_interpret() if interpret is None else interpret
    xp, n = _pad_to_tile(x[None])
    q, s = quantize_tiles(xp[0], interpret=interpret)
    return q[:n], s


def dequantize_flat(q, s, n=None, *, dtype=jnp.float32, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    qp, n_orig = _pad_to_tile(q[None])
    out = dequantize_tiles(qp[0], s, dtype=dtype, interpret=interpret)
    return out[: (n if n is not None else n_orig)]


def quantized_delta_push(theta, theta_ref, *, interpret=None):
    """Beyond-paper compressed model push: int8(θ − θ_ref) + scales.

    Returns (codes_tree, scales_tree); reconstruct with
    :func:`quantized_delta_pull`. Wire size ≈ params × 1 byte + 4/TILE.
    """
    def leaf(t, r):
        d = (t.astype(jnp.float32) - r.astype(jnp.float32)).ravel()
        return quantize_flat(d, interpret=interpret)

    pairs = jax.tree.map(leaf, theta, theta_ref)
    codes = jax.tree.map(lambda p: p[0], pairs,
                         is_leaf=lambda x: isinstance(x, tuple))
    scales = jax.tree.map(lambda p: p[1], pairs,
                          is_leaf=lambda x: isinstance(x, tuple))
    return codes, scales


def quantized_delta_pull(codes, scales, theta_ref, *, interpret=None):
    def leaf(q, s, r):
        d = dequantize_flat(q, s, n=int(np.prod(r.shape)),
                            interpret=interpret)
        return (r.astype(jnp.float32) + d.reshape(r.shape)).astype(r.dtype)

    return jax.tree.map(leaf, codes, scales, theta_ref)
