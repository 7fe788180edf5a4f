"""Checkpoint round-trips for nested pytrees (params + optimizer states)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint, configs, optim
from repro.engine.flat import FlatModel
from repro.models import build


def test_roundtrip_nested(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16),
                  "d": [jnp.zeros((2, 2)), jnp.full((1,), 7.0)]}}
    path = str(tmp_path / "ckpt")
    checkpoint.save(path, tree, meta={"round": 12})
    back, meta = checkpoint.restore(path, tree)
    assert meta["round"] == 12
    for x, y in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_roundtrip_model_and_opt(tmp_path):
    cfg = configs.reduced(configs.get_config("tinyllama-1.1b"))
    model = build(cfg)
    params = model.init(jax.random.key(0))
    opt = optim.momentum(0.1)
    state = {"params": params, "opt": opt.init(params)}
    path = str(tmp_path / "full")
    checkpoint.save(path, state, meta={"arch": cfg.name})
    back, meta = checkpoint.restore(path, state)
    assert meta["arch"] == cfg.name
    a = jax.tree.leaves(back)
    b = jax.tree.leaves(state)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_shape_mismatch_rejected(tmp_path):
    tree = {"w": jnp.zeros((3, 3))}
    path = str(tmp_path / "bad")
    checkpoint.save(path, tree)
    try:
        checkpoint.restore(path, {"w": jnp.zeros((4, 4))})
        raise AssertionError("should have raised")
    except ValueError:
        pass


# --------------------------------------------- parametrized round-trip grid


def _family_params(family: str):
    if family == "cnn":
        from repro.models.tasks import cnn_task
        return cnn_task().init_params(0)
    from repro.models.tasks import mf_task
    return mf_task().init_params(0)


@pytest.mark.parametrize("family", ["cnn", "mf"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("kind", ["pytree", "flatmodel"])
def test_roundtrip_grid(tmp_path, family, dtype, kind):
    """Task families × leaf dtypes × FlatModel vs pytree templates."""
    params = _family_params(family)
    if jnp.issubdtype(dtype, jnp.integer):
        # small exact integers (step counters): cast survives the fp32
        # flat buffer too (exact up to 2^24)
        tree = jax.tree.map(
            lambda x: (np.arange(x.size).reshape(x.shape) % 97
                       ).astype(dtype), params)
    else:
        tree = jax.tree.map(lambda x: jnp.asarray(x, dtype), params)
    obj = FlatModel.pack(tree) if kind == "flatmodel" else tree
    path = str(tmp_path / f"{family}-{np.dtype(dtype).name}-{kind}")
    checkpoint.save(path, obj, meta={"family": family})
    back, meta = checkpoint.restore(path, obj)
    assert meta["family"] == family
    if kind == "flatmodel":
        assert isinstance(back, FlatModel)
        np.testing.assert_array_equal(np.asarray(back.buffer),
                                      np.asarray(obj.buffer))
        back = back.tree
    for x, y in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


# ------------------------------------------------------- failure-mode rails


def test_slash_key_collision_raises(tmp_path):
    """A dict key containing '/' must not silently overwrite the
    genuinely nested path it collides with."""
    tree = {"attn/wo": jnp.zeros((2,)), "attn": {"wo": jnp.ones((2,))}}
    with pytest.raises(ValueError, match="collision"):
        checkpoint.save(str(tmp_path / "clash"), tree)


def test_slash_key_without_collision_roundtrips(tmp_path):
    tree = {"attn/wo": jnp.arange(3, dtype=jnp.float32)}
    path = str(tmp_path / "slashed")
    checkpoint.save(path, tree)
    back, _ = checkpoint.restore(path, tree)
    np.testing.assert_array_equal(np.asarray(back["attn/wo"]),
                                  np.asarray(tree["attn/wo"]))


def test_dtype_companion_collision_raises(tmp_path):
    """A literal '__dtype__/...' key colliding with a bf16 leaf's dtype
    companion entry is caught too."""
    tree = {"__dtype__": {"w": jnp.zeros((2,))},
            "w": jnp.ones((2,), jnp.bfloat16)}
    with pytest.raises(ValueError, match="collision"):
        checkpoint.save(str(tmp_path / "dclash"), tree)


def test_missing_key_clear_error(tmp_path):
    path = str(tmp_path / "partial")
    checkpoint.save(path, {"layer0": jnp.zeros((2,)),
                           "layer1": jnp.ones((2,))})
    with pytest.raises(KeyError) as exc:
        checkpoint.restore(path, {"layer0": jnp.zeros((2,)),
                                  "layer2": jnp.zeros((2,))})
    msg = str(exc.value)
    assert "layer2" in msg                  # which key is missing
    assert "layer0" in msg and "layer1" in msg   # what the checkpoint has


# ------------------------------------------------------- sharding threading


def test_restore_with_single_sharding(tmp_path):
    tree = {"w": jnp.arange(4, dtype=jnp.float32)}
    path = str(tmp_path / "sh")
    checkpoint.save(path, tree)
    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    back, _ = checkpoint.restore(path, tree, shardings=sh)
    assert back["w"].sharding == sh


def test_restore_with_sharding_pytree(tmp_path):
    tree = {"a": jnp.zeros((2,)), "b": jnp.ones((3,))}
    path = str(tmp_path / "shtree")
    checkpoint.save(path, tree)
    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    back, _ = checkpoint.restore(path, tree, shardings={"a": sh, "b": sh})
    assert back["a"].sharding == sh and back["b"].sharding == sh
    with pytest.raises(ValueError, match="leaves"):
        checkpoint.restore(path, tree, shardings={"a": sh})


def test_restore_flatmodel_with_flat_shardings(tmp_path):
    from repro.sharding import flat_shardings

    fm = FlatModel.pack({"w": jnp.arange(6, dtype=jnp.float32)})
    path = str(tmp_path / "fmsh")
    checkpoint.save(path, fm)
    sh = flat_shardings(jax.make_mesh(
        (1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2))
    back, _ = checkpoint.restore(path, fm, shardings=sh)
    assert isinstance(back, FlatModel)
    assert back.buffer.sharding == sh.vec
    np.testing.assert_array_equal(np.asarray(back.buffer),
                                  np.asarray(fm.buffer))
