"""Ahead-of-time compiles of the session path for a described TPU v5e.

The fused aggregation kernels only ever run in interpret mode on the CPU,
which cannot see the TPU's tiling rules or its VMEM limit. Here each
``pallas_call`` of the session path, its ``shard_map`` twin on a 4-chip
mesh and the paper-cnn cohort train step are compiled by the TPU compiler
for a ``v5e:2x2`` topology that is described, not attached. Nothing runs:
a compile that passes says the chip's compiler accepts the program.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and a worker that is never
handed this file must not try.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused
from repro.models.tasks import cnn_task

N = 136672                    # paper-cnn flat width (LeNet (6, 16), 32×32×3)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))


def _kernel_args(p, n, sharding_of, masked):
    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding_of(spec))
    # sealed rows travel as uint32 bit patterns (fused.bits)
    args = [s((p, n), jnp.uint32 if masked else jnp.float32,
              P(None, "model")),
            s((p,), jnp.float32, P(None)),
            s((n,), jnp.float32, P("model"))]
    if masked:
        args += [s((p, p), jnp.uint32, P(None, None)),
                 s((p, p), jnp.int32, P(None, None))]
    return args


VARIANTS = [pytest.param(False, False, id="onepass"),
            pytest.param(True, False, id="quantize"),
            pytest.param(False, True, id="unmask"),
            pytest.param(True, True, id="unmask-quantize")]


@pytest.mark.parametrize("quantize,masked", VARIANTS)
@pytest.mark.parametrize("p", [2, 5, 10])
def test_fused_kernel_compiles(one_chip, p, quantize, masked):
    args = _kernel_args(p, N, lambda spec: one_chip, masked)
    if masked:
        args.append(jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip))
    compiled = fused._fused.lower(
        *args, tile=fused.tile_for(N, p), n_valid=N if masked else None,
        quantize=quantize, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("quantize,masked", VARIANTS)
def test_sharded_kernel_compiles(mesh4, quantize, masked):
    p = 5
    n = fused.shard_align(N, 4)
    args = _kernel_args(p, n, lambda spec: NamedSharding(mesh4, spec), masked)
    if masked:
        f = fused._sharded_unmask(mesh4, "model", quantize, True, False, N)
    else:
        f = fused._sharded_onepass(mesh4, "model", quantize, True, False)
    compiled = f.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # per-device bytes: the (P, n/4) shard of the stack, not the whole
    assert compiled.memory_analysis().argument_size_in_bytes < p * n * 4


def test_cohort_step_compiles(one_chip):
    """The vmapped (S, N) paper-cnn train step at S=10, B=20 (the
    per-batch form the engine takes at this volume)."""
    from repro.engine.cohort import _cohort_ops

    task = cnn_task()
    opt, step, _ = _cohort_ops(task)
    S, B = 10, 20

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    buf = s((S, task.flat_spec.n), jnp.float32)
    state = jax.tree.map(lambda l: s(l.shape, l.dtype),
                         jax.eval_shape(opt.init, buf))
    compiled = step.lower(buf, state, s((S, B, 32, 32, 3), jnp.float32),
                          s((S, B), jnp.int32), s((S, B), jnp.float32),
                          s((S,), jnp.bool_)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30
