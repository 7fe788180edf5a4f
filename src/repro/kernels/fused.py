"""Whole-model one-pass aggregation kernels (the FlatModel engine's core).

The per-leaf path (:mod:`repro.kernels.aggregate` via ``aggregate_pytree``)
launches one ``pallas_call`` per pytree leaf, plus a ravel/stack/pad round
trip for each — per-call overhead that dominates for many-leaf models. Here
the whole model is a single ``(P, N)`` stack of flat fp32 buffers and
aggregation is ONE ``pallas_call``:

* :func:`aggregate_flat_onepass` — masked weighted mean over P replicas.
  Integer-leaf positions (``int_mask``) are rounded to nearest *inside*
  the kernel, so optimizer counters survive aggregation exactly (PR-2
  semantics) without a second pass.
* :func:`aggregate_quantize_flat` — the fused aggregate→quantize variant:
  emits the fp32 mean *and* int8 codes + per-subtile scales straight from
  the accumulator, saving the extra HBM round trip of a separate quantize
  call (mean is written once; codes/scales come from values already in
  VMEM).
* :func:`unmask_aggregate_flat` / :func:`unmask_aggregate_quantize_flat` —
  the same math on sealed rows, with the secure-aggregation mask removed
  in-kernel (see the PRG section below).

All four are one kernel body (:func:`_fused_kernel`) behind one launch
wrapper (:func:`_fused`).

Layout: the flat axis is viewed as ``(N/128, 128)`` rows of 128 lanes, so
every block is dense in the TPU's (8, 128) tiling — a ``(P, tile)`` block
would pad P (and every 1-row intermediate) to 8 sublanes. One SUBTILE is
128 such rows; the kernel walks its block one SUBTILE chunk at a time, so
in-kernel temporaries are a few ``(128, 128)`` arrays whatever the tile.

Tiling: ``tile_for()`` picks the largest multiple of ``SUBTILE`` (= the
quantization granularity, 16384 lanes, shared with
:mod:`repro.kernels.quantize`) whose pipelined blocks (:func:`block_bytes`)
fit the VMEM budget for ``P`` replicas. Bigger tiles mean fewer grid steps
— less per-step overhead and better DMA pipelining on TPU. Quantization
scales are always per-SUBTILE regardless of the chosen tile, so codes are
bit-identical to ``quantize_ref(mean)`` for any tiling.

Zero-total-weight is a caller error and raises in the wrappers
(:func:`repro.utils.pytree.tree_weighted_mean` documents the contract);
the kernels themselves assume ``sum(w) > 0``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBTILE = 16384               # quantization granularity (= quantize.TILE)
LANES = 128
_CHUNK = SUBTILE // LANES     # rows of one SUBTILE in the (rows, 128) view
_VMEM_BUDGET = 12 * 1024 * 1024    # bytes of pipelined blocks per grid step
_VMEM_HEADROOM = 4 * 1024 * 1024   # chunk temporaries + compiler scratch
_VMEM_CAP = 96 * 1024 * 1024       # scoped limit ceiling (v5e VMEM: 128 MiB)


def block_bytes(tile: int, p: int) -> int:
    """VMEM bytes of one grid step's blocks, each double-buffered while
    the grid pipelines: the ``(P, tile)`` fp32 stack, the fp32 int-mask
    and mean rows, the int8 codes and the scales (one padded (8, 128)
    fp32 tile per SUBTILE). Counted for the fused quantize variant, the
    largest of the four kernels."""
    return 2 * ((4 * p + 4 + 4 + 1) * tile + (tile // SUBTILE) * 8 * LANES * 4)


def tile_for(n: int, p: int) -> int:
    """Largest SUBTILE multiple whose blocks fit the VMEM budget, floored
    at one SUBTILE so huge P still quantizes correctly (the scoped VMEM
    limit then grows to fit, see :func:`_vmem_limit`) and capped at ``n``
    rounded up to SUBTILE."""
    fit = _VMEM_BUDGET // block_bytes(SUBTILE, p)
    need = -(-n // SUBTILE)
    return max(1, min(need, fit)) * SUBTILE


def _vmem_limit(tile: int, p: int) -> int:
    limit = block_bytes(tile, p) + _VMEM_HEADROOM
    if limit > _VMEM_CAP:
        raise ValueError(f"P={p} replicas need {limit} B of VMEM at one "
                         f"SUBTILE; the cap is {_VMEM_CAP} B")
    return limit


def shard_align(n: int, shards: int) -> int:
    """Padded total length so each of ``shards`` equal contiguous
    model-axis shards is a SUBTILE multiple.

    Padding only at the global tail would misalign per-shard subtile
    boundaries; aligning every shard keeps the global SUBTILE grid
    identical to the single-device layout, so per-SUBTILE quantization
    scales — and therefore int8 codes — stay bit-identical."""
    per = -(-n // (shards * SUBTILE)) * SUBTILE
    return shards * per


def _fused_kernel(*refs, tile, n_valid, quantize, masked):
    """One grid step: ``tile`` lanes of all P rows → mean (+ codes/scales).

    refs: w (P,) SMEM, x (P, tile/128, 128), int mask (tile/128, 128)
    [, seeds (P, R), signs (P, R), base (1,) — all SMEM int32] → mean
    [, codes, scales (tile/SUBTILE, 1, 128)].
    """
    if masked:
        w_ref, x_ref, m_ref, seed_ref, sign_ref, base_ref, *outs = refs
    else:
        w_ref, x_ref, m_ref, *outs = refs
    n_rows = x_ref.shape[0]
    total = jax.lax.fori_loop(1, n_rows, lambda p, t: t + w_ref[p], w_ref[0])
    step = pl.program_id(0)

    def chunk(k, carry):
        rows = pl.ds(pl.multiple_of(k * _CHUNK, _CHUNK), _CHUNK)
        if masked:
            first = base_ref[0] + step * tile + k * SUBTILE
            lanes = (first
                     + jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, LANES), 0)
                     * LANES
                     + jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, LANES), 1))

            def load(p):
                return _unmask_row(x_ref[p, rows, :], seed_ref, sign_ref, p,
                                   lanes, n_valid)
        else:
            def load(p):
                return x_ref[p, rows, :]

        acc = jax.lax.fori_loop(1, n_rows,
                                lambda p, a: a + load(p) * w_ref[p],
                                load(0) * w_ref[0])
        acc = acc / total
        acc = jnp.where(m_ref[rows, :] > 0, jnp.round(acc), acc)
        outs[0][rows, :] = acc
        if quantize:
            # quantize the mean while it is still in VMEM: per-SUBTILE
            # absmax scale, the exact arithmetic of quantize_ref
            amax = jnp.max(jnp.max(jnp.abs(acc), axis=1, keepdims=True),
                           axis=0, keepdims=True)                 # (1, 1)
            scale = jnp.maximum(amax, 1e-12) / 127.0
            q = jnp.clip(jnp.round(acc / scale), -127, 127)
            outs[1][rows, :] = q.astype(jnp.int8)
            outs[2][k, :, :] = jnp.broadcast_to(scale, (1, LANES))
        return carry

    jax.lax.fori_loop(0, tile // SUBTILE, chunk, 0)


@functools.partial(jax.jit,
                   static_argnames=("tile", "n_valid", "quantize", "interpret"))
def _fused(x, w, int_mask, seeds=None, signs=None, base=None, *, tile: int,
           n_valid=None, quantize: bool, interpret: bool):
    """Launch :func:`_fused_kernel` over an unpadded ``(P, n)`` stack.

    Padding to a tile multiple, the ``(rows, 128)`` view and the final
    slices are inside this jit, so XLA fuses them around the kernel.
    ``seeds``/``signs`` (P, R) and ``base`` (1,) select the unmask
    variant; ``n_valid`` is then the global count of sealed lanes, and
    ``x`` holds sealed bit patterns, moved as uint32 (see :func:`bits`).
    """
    masked = seeds is not None
    if masked:
        x = bits(x)
    P, n = x.shape
    pad = (-n) % tile
    rows = (n + pad) // LANES
    x3 = jnp.pad(x, [(0, 0), (0, pad)]).reshape(P, rows, LANES)
    m2 = jnp.pad(int_mask, (0, pad)).reshape(rows, LANES)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    row_block = pl.BlockSpec((tile // LANES, LANES), lambda i: (i, 0))
    in_specs = [smem, pl.BlockSpec((P, tile // LANES, LANES),
                                   lambda i: (0, i, 0)), row_block]
    args = [w.astype(jnp.float32), x3, m2]
    if masked:
        in_specs += [smem] * 3
        args += [jax.lax.bitcast_convert_type(seeds, jnp.int32), signs, base]
    out_specs = [row_block]
    out_shape = [jax.ShapeDtypeStruct((rows, LANES), jnp.float32)]
    if quantize:
        out_specs += [row_block,
                      pl.BlockSpec((tile // SUBTILE, 1, LANES),
                                   lambda i: (i, 0, 0))]
        out_shape += [jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
                      jax.ShapeDtypeStruct((rows // _CHUNK, 1, LANES),
                                           jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_fused_kernel, tile=tile, n_valid=n_valid,
                          quantize=quantize, masked=masked),
        grid=((n + pad) // tile,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(tile, P)),
        interpret=interpret,
    )(*args)
    mean = outs[0].reshape(-1)[:n]
    if not quantize:
        return mean
    return (mean, outs[1].reshape(-1)[:n],
            outs[2][:, 0, 0][: -(-n // SUBTILE)])


def _float_mask(int_mask, n):
    if int_mask is None:
        return jnp.zeros((n,), jnp.float32)
    return jnp.asarray(int_mask, jnp.float32)


def aggregate_flat_onepass(x, w, int_mask=None, *, interpret: bool = False):
    """x: (P, N) flat fp32 models; w: (P,). One kernel call → mean (N,).

    ``int_mask`` marks integer-leaf positions (rounded in-kernel); None
    means all-float.
    """
    P, N = x.shape
    return _fused(x, w, _float_mask(int_mask, N), tile=tile_for(N, P),
                  quantize=False, interpret=interpret)


def aggregate_quantize_flat(x, w, int_mask=None, *, interpret: bool = False):
    """Fused aggregate→quantize: one kernel call → (mean (N,), codes int8
    (N,), scales (ceil(N/SUBTILE),)).

    Codes/scales match ``quantize_ref(mean)`` applied to the SUBTILE-padded
    mean; the caller keeps ``N`` to slice codes back down.
    """
    P, N = x.shape
    return _fused(x, w, _float_mask(int_mask, N), tile=tile_for(N, P),
                  quantize=True, interpret=interpret)


# ---------------------------------------------------------------------------
# Sharded variants: the same one-pass aggregation per model-axis shard
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _sharded_onepass(mesh, model_axis: str, quantize: bool, use_kernel: bool,
                     interpret: bool):
    """jit(shard_map) running the one-pass aggregation per model-axis shard.

    Inputs arrive padded to ``shard_align`` lengths, so every local block
    is a SUBTILE multiple and the kernel path recomputes its VMEM tile
    *per local shard* (``tile_for(local_n, P)``). ``check_vma=False``
    because ``pallas_call`` has no replication rule under shard_map.
    """
    from jax.sharding import PartitionSpec as P

    def local(x, w, m):
        if use_kernel:
            if quantize:
                return aggregate_quantize_flat(x, w, m, interpret=interpret)
            return (aggregate_flat_onepass(x, w, m, interpret=interpret),)
        # jnp local block: the exact contraction of ops._jnp_onepass —
        # elementwise over N, so sharding N cannot change any value.
        total = jnp.sum(w)
        mean = jnp.tensordot(w, x, axes=(0, 0)) / total
        mean = jnp.where(m > 0, jnp.round(mean), mean)
        if not quantize:
            return (mean,)
        t = mean.reshape(-1, SUBTILE)          # local n is SUBTILE-aligned
        scale = jnp.maximum(jnp.max(jnp.abs(t), axis=1), 1e-12) / 127.0
        q = jnp.clip(jnp.round(t / scale[:, None]), -127, 127)
        return mean, q.reshape(-1).astype(jnp.int8), scale

    M = model_axis
    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(None, M), P(None), P(M)),
                      out_specs=tuple([P(M)] * (3 if quantize else 1)),
                      check_vma=False)
    return jax.jit(f)


def _pad_sharded(x, int_mask, mesh, model_axis):
    P, N = x.shape
    int_mask = _float_mask(int_mask, N)
    pad = shard_align(N, mesh.shape[model_axis]) - N
    if pad:
        x = jnp.pad(x, [(0, 0), (0, pad)])
        int_mask = jnp.pad(int_mask, (0, pad))
    return x, int_mask, N


def aggregate_flat_onepass_sharded(x, w, int_mask=None, *, mesh,
                                   model_axis: str = "model",
                                   use_kernel: bool = True,
                                   interpret: bool = False):
    """Sharded :func:`aggregate_flat_onepass`: mean ``(N,)`` sharded over
    ``model_axis``. Bit-identical to the single-device path (the weighted
    mean is elementwise over N)."""
    xp, mp, n = _pad_sharded(x, int_mask, mesh, model_axis)
    (mean,) = _sharded_onepass(mesh, model_axis, False, use_kernel,
                               interpret)(xp, w, mp)
    return mean[:n]


def aggregate_quantize_flat_sharded(x, w, int_mask=None, *, mesh,
                                    model_axis: str = "model",
                                    use_kernel: bool = True,
                                    interpret: bool = False):
    """Sharded fused aggregate→quantize.

    Per-shard lengths are SUBTILE-aligned (:func:`shard_align`), so the
    global subtile grid — and with it codes and scales — is bit-identical
    to :func:`aggregate_quantize_flat` on one device; trailing pad
    subtiles are sliced off before returning.
    """
    xp, mp, n = _pad_sharded(x, int_mask, mesh, model_axis)
    mean, q, s = _sharded_onepass(mesh, model_axis, True, use_kernel,
                                  interpret)(xp, w, mp)
    return mean[:n], q[:n], s[: -(-n // SUBTILE)]


# ---------------------------------------------------------------------------
# Secure-aggregation variants: in-kernel mask PRG + exact unmask
# (repro.secureagg, docs/SECUREAGG.md)
# ---------------------------------------------------------------------------
#
# A trainer seals its flat buffer by shifting the fp32 *bit patterns*
# additively in the uint32 ring; the aggregator removes the shift exactly
# (ring subtraction) and then runs the IDENTICAL aggregate→quantize math,
# so masked results are bit-identical to the plain kernels — an fp-domain
# mask could never be (fp addition is non-associative).
#
# The PRG is counter-based with the *global* lane index as counter
# (base + grid step·tile + position in the block, where base is
# axis_index·local_n under shard_map), so mask words are independent of
# tiling and sharding and the sealed buffer a trainer produced on one
# device unmasks on any mesh. It mirrors ``repro.secureagg.prg.prg_word``
# bit-exactly — change both together (tests/test_secureagg.py pins them
# against each other).

_PRG_MIX1 = 0x7FEB352D
_PRG_MIX2 = 0x846CA68B


def bits(y):
    """Sealed fp32 rows as their uint32 bit patterns. A sealed word is
    any 32-bit pattern, NaNs included, and an fp32 copy on the TPU need
    not keep a NaN's payload; uint32 moves every bit as it is."""
    return jax.lax.bitcast_convert_type(y, jnp.uint32)


def _mix32(x):
    x = (x ^ (x >> 16)) * jnp.uint32(_PRG_MIX1)
    x = (x ^ (x >> 15)) * jnp.uint32(_PRG_MIX2)
    return x ^ (x >> 16)


def _prg_u32(seed, ctr):
    x = ctr ^ (seed * jnp.uint32(_PRG_MIX1))
    x = _mix32(x) + seed
    return _mix32(x)


def _mask_words(seeds, signs, lanes):
    """sum_j sign_j · PRG(seed_j, lane) in the uint32 ring.

    seeds/signs: (..., R); lanes: broadcastable uint32 counters. A −1
    sign cast to uint32 is 2^32−1, i.e. ring negation — no branching.
    """
    words = _prg_u32(seeds[..., :, None].astype(jnp.uint32),
                     lanes[..., None, :])                  # (..., R, L)
    sgn = signs[..., :, None].astype(jnp.uint32)
    return jnp.sum(words * sgn, axis=-2, dtype=jnp.uint32)  # (..., L)


@jax.jit
def apply_mask_flat(buf, seeds, signs):
    """Seal a flat fp32 buffer: bits(buf) ⊞ mask, lane l = PRG counter l.

    Exact inverse: ``apply_mask_flat(sealed, seeds, -signs)``.
    """
    lanes = jnp.arange(buf.shape[0], dtype=jnp.uint32)
    y = jax.lax.bitcast_convert_type(buf, jnp.uint32)
    y = y + _mask_words(seeds, signs, lanes)
    return jax.lax.bitcast_convert_type(y, jnp.float32)


def _unmask_bits(y, seeds, signs, lanes, n_valid):
    """Remove each row's mask (uint32 ring) and bitcast back to fp32.

    y: (P, L) masked bit patterns (uint32, or fp32); seeds/signs: (P, R); lanes:
    (1, L) global lane counters. Lanes >= n_valid were never masked
    (padding) and pass through untouched, so pad lanes stay exact fp32
    zeros and the downstream math sees exactly what the plain path sees.
    """
    y = bits(y)
    mask = jnp.zeros_like(y)
    for j in range(seeds.shape[1]):               # R is small and static
        words = _prg_u32(seeds[:, j:j + 1].astype(jnp.uint32), lanes)
        mask = mask + words * signs[:, j:j + 1].astype(jnp.uint32)
    x = jnp.where(lanes < jnp.uint32(n_valid), y - mask, y)
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def _unmask_row(y, seed_ref, sign_ref, p, lanes, n_valid):
    """In-kernel twin of :func:`_unmask_bits` for row ``p`` of one chunk:
    y the row's uint32 sealed words, seeds/signs SMEM int32 scalars (bit
    patterns), lanes the chunk's int32 global lane counters."""
    ctr = jax.lax.bitcast_convert_type(lanes, jnp.uint32)

    def word(j, mask):
        seed = jax.lax.bitcast_convert_type(
            jnp.full(lanes.shape, seed_ref[p, j], jnp.int32), jnp.uint32)
        sign = jax.lax.bitcast_convert_type(
            jnp.full(lanes.shape, sign_ref[p, j], jnp.int32), jnp.uint32)
        return mask + _prg_u32(seed, ctr) * sign

    mask = jax.lax.fori_loop(0, seed_ref.shape[1], word,
                             jnp.zeros(lanes.shape, jnp.uint32))
    x = jnp.where(lanes < n_valid, y - mask, y)
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def unmask_aggregate_flat(y, w, int_mask=None, *, seeds, signs,
                          interpret: bool = False):
    """Fused unmask→aggregate: y (P, N) sealed rows (uint32 bit patterns,
    or fp32 that were never copied as fp32), seeds/signs (P, R) per-row
    mask derivation → mean (N,), bit-identical to
    :func:`aggregate_flat_onepass` on the unsealed rows."""
    P, N = y.shape
    return _fused(y, w, _float_mask(int_mask, N),
                  jnp.asarray(seeds, jnp.uint32), jnp.asarray(signs, jnp.int32),
                  jnp.zeros((1,), jnp.int32), tile=tile_for(N, P), n_valid=N,
                  quantize=False, interpret=interpret)


def unmask_aggregate_quantize_flat(y, w, int_mask=None, *, seeds, signs,
                                   interpret: bool = False):
    """Fused unmask→aggregate→quantize: (mean, int8 codes, scales) bit-
    identical to :func:`aggregate_quantize_flat` on the unsealed rows."""
    P, N = y.shape
    return _fused(y, w, _float_mask(int_mask, N),
                  jnp.asarray(seeds, jnp.uint32), jnp.asarray(signs, jnp.int32),
                  jnp.zeros((1,), jnp.int32), tile=tile_for(N, P), n_valid=N,
                  quantize=True, interpret=interpret)


@functools.lru_cache(maxsize=32)
def _sharded_unmask(mesh, model_axis: str, quantize: bool, use_kernel: bool,
                    interpret: bool, n_valid: int):
    """jit(shard_map) unmask→aggregate per model-axis shard.

    Each shard's PRG counters start at ``axis_index · local_n`` — with
    :func:`shard_align` padding, shard r holds exactly the contiguous
    global lanes [r·local_n, (r+1)·local_n), so the regenerated mask
    words match what the (single-device) sealer produced and the
    unmasked values — hence the downstream mean/codes/scales — are
    bit-identical to the single-device masked path and to the plain
    sharded path.
    """
    from jax.sharding import PartitionSpec as P

    def local(y, w, m, seeds, signs):
        local_n = y.shape[1]
        base = jax.lax.axis_index(model_axis) * local_n
        if use_kernel:
            out = _fused(y, w, m, seeds, signs,
                         base.astype(jnp.int32).reshape(1),
                         tile=tile_for(local_n, y.shape[0]), n_valid=n_valid,
                         quantize=quantize, interpret=interpret)
            return out if quantize else (out,)
        lanes = (base.astype(jnp.uint32)
                 + jnp.arange(local_n, dtype=jnp.uint32)[None, :])
        x = _unmask_bits(y, seeds, signs, lanes, n_valid)
        # identical local block to _sharded_onepass's jnp path
        total = jnp.sum(w)
        mean = jnp.tensordot(w, x, axes=(0, 0)) / total
        mean = jnp.where(m > 0, jnp.round(mean), mean)
        if not quantize:
            return (mean,)
        t = mean.reshape(-1, SUBTILE)
        scale = jnp.maximum(jnp.max(jnp.abs(t), axis=1), 1e-12) / 127.0
        q = jnp.clip(jnp.round(t / scale[:, None]), -127, 127)
        return mean, q.reshape(-1).astype(jnp.int8), scale

    M = model_axis
    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(None, M), P(None), P(M), P(None, None),
                                P(None, None)),
                      out_specs=tuple([P(M)] * (3 if quantize else 1)),
                      check_vma=False)
    return jax.jit(f)


def unmask_aggregate_flat_sharded(y, w, int_mask=None, *, seeds, signs,
                                  mesh, model_axis: str = "model",
                                  use_kernel: bool = True,
                                  interpret: bool = False):
    """Sharded :func:`unmask_aggregate_flat` (see :func:`_sharded_unmask`)."""
    yp, mp, n = _pad_sharded(bits(y), int_mask, mesh, model_axis)
    seeds = jnp.asarray(seeds, jnp.uint32)
    signs = jnp.asarray(signs, jnp.int32)
    (mean,) = _sharded_unmask(mesh, model_axis, False, use_kernel,
                              interpret, n)(yp, w, mp, seeds, signs)
    return mean[:n]


def unmask_aggregate_quantize_flat_sharded(y, w, int_mask=None, *, seeds,
                                           signs, mesh,
                                           model_axis: str = "model",
                                           use_kernel: bool = True,
                                           interpret: bool = False):
    """Sharded :func:`unmask_aggregate_quantize_flat`."""
    yp, mp, n = _pad_sharded(bits(y), int_mask, mesh, model_axis)
    seeds = jnp.asarray(seeds, jnp.uint32)
    signs = jnp.asarray(signs, jnp.int32)
    mean, q, s = _sharded_unmask(mesh, model_axis, True, use_kernel,
                                 interpret, n)(yp, w, mp, seeds, signs)
    return mean[:n], q[:n], s[: -(-n // SUBTILE)]
