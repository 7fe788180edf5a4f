"""Secure aggregation (`repro.secureagg`, docs/SECUREAGG.md).

Four layers, mirroring the subsystem:

* primitives — host/device PRG bit-parity, Shamir threshold semantics,
  DH pair-seed symmetry, threshold clamping;
* sealing — seal/unseal exactness per payload kind, sealed bits actually
  differ from plaintext;
* kernels — the fused unmask-aggregate(-quantize) path is bit-identical
  to the plain kernels (mean, int8 codes AND scales), including the
  sharded dispatch;
* protocol — secure sessions progress, nothing plaintext ever travels,
  the share-threshold gate holds, and secure_agg=None stays zero-cost.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModestConfig, TrainConfig
from repro.core import messages as M
from repro.core.node import ModestNode
from repro.core.tasks import AbstractTask
from repro.engine.flat import FlatModel, FlatSpec
from repro.kernels.fused import _prg_u32, apply_mask_flat
from repro.kernels.ops import aggregate_flatmodel, masked_aggregate_flatmodel
from repro.secureagg import PairwiseMasker, SealedModel, threshold
from repro.secureagg import prg, shamir
from repro.sim.clock import Simulator
from repro.sim.network import Network
from repro.sim.runner import ModestSession

MCFG = ModestConfig(n_nodes=20, sample_size=4, n_aggregators=2,
                    success_fraction=1.0, ping_timeout=1.0,
                    activity_window=20, secure_agg="masked")
TASK = AbstractTask(model_bytes_=100_000)


@pytest.fixture(scope="module")
def spec():
    """Small synthetic model with an awkward total (exercises subtile
    padding) and an integer leaf (exercises the int mask path)."""
    tree = {"w": np.zeros((123, 7), np.float32),
            "b": np.zeros((11,), np.float32),
            "steps": np.zeros((3,), np.int32)}
    return FlatSpec.from_tree(tree)


def _models(spec, s=5, seed=0):
    rng = np.random.default_rng(seed)
    return [FlatModel(jnp.asarray(rng.standard_normal(spec.n), jnp.float32),
                      spec) for _ in range(s)]


def _sealed(spec, masker, round_k=7, s=5, seed=0):
    roster = tuple(f"n{i}" for i in range(s))
    models = _models(spec, s, seed)
    sealed = [masker.seal(m, roster[i], round_k, roster, spec.nbytes)
              for i, m in enumerate(models)]
    secrets = {nid: masker.secret(nid, round_k) for nid in roster}
    return models, sealed, secrets


# --------------------------------------------------------------- primitives


def test_prg_host_device_bit_parity():
    """The in-kernel PRG and the host-side protocol PRG must agree bit
    for bit — the aggregator regenerates in-kernel exactly the words the
    trainer added on the host."""
    rng = np.random.default_rng(0)
    seeds = rng.integers(0, 2**32, size=64, dtype=np.uint64)
    ctrs = np.concatenate([rng.integers(0, 2**32, size=60, dtype=np.uint64),
                           [0, 1, 2**31, 2**32 - 1]])
    host = np.array([prg.prg_word(int(s), int(c))
                     for s, c in zip(seeds, ctrs)], np.uint32)
    dev = _prg_u32(jnp.asarray(seeds, jnp.uint32)[None, :],
                   jnp.asarray(ctrs, jnp.uint32)[None, :])
    assert np.array_equal(host, np.asarray(dev)[0])


def test_shamir_roundtrip_and_threshold_gate():
    secret = prg.round_secret(42, "n3", 9)
    shares = shamir.split(secret, "n3", 9, n=5, t=4)
    assert len(shares) == 5 and [x for x, _ in shares] == [1, 2, 3, 4, 5]
    assert shamir.reconstruct(shares, 4) == secret
    assert shamir.reconstruct(shares[1:], 4) == secret   # any t-subset
    with pytest.raises(ValueError):
        shamir.reconstruct(shares[:3], 4)                # below threshold
    with pytest.raises(ValueError):
        shamir.reconstruct([shares[0]] * 4, 4)           # x must be distinct


def test_dh_pair_seed_symmetry():
    sk_a = prg.round_secret(0, "a", 3)
    sk_b = prg.round_secret(0, "b", 3)
    assert prg.pair_seed(sk_a, prg.public_key(sk_b)) == \
        prg.pair_seed(sk_b, prg.public_key(sk_a))
    # personal seed differs from every pair seed (it is what keeps a
    # cohort-of-one row non-plaintext)
    assert prg.personal_seed(sk_a) != prg.pair_seed(sk_a,
                                                    prg.public_key(sk_b))


def test_threshold_majority_plus_one_clamped():
    assert [threshold(s) for s in (1, 2, 3, 4, 5, 10)] == [1, 2, 3, 3, 4, 6]


# ------------------------------------------------------------------ sealing


def test_seal_unseal_flat_is_exact_and_actually_masks(spec):
    masker = PairwiseMasker(0)
    models, sealed, secrets = _sealed(spec, masker)
    for m, sm in zip(models, sealed):
        assert isinstance(sm, SealedModel) and sm.kind == "flat"
        assert sm.nbytes == spec.nbytes                  # size-preserving
        # sealed bits are (essentially) uncorrelated with the plaintext
        same = np.mean(np.asarray(sm.payload.buffer) == np.asarray(m.buffer))
        assert same < 0.001
        # exact bit roundtrip through the reconstructed secret
        back = masker.unseal_flat(sm, secrets[sm.sender])
        assert np.array_equal(np.asarray(back.buffer), np.asarray(m.buffer))


def test_seal_unseal_scalar_and_bytes_kinds():
    masker = PairwiseMasker(1)
    roster = ("a", "b", "c")
    x = np.float32(3.25)
    sm = masker.seal(x, "b", 4, roster, 4)
    assert sm.kind == "scalar" and sm.payload != int(x.view(np.uint32))
    back = masker.unseal_scalar(sm, masker.secret("b", 4))
    assert back.dtype == np.float32 and back == x        # bit-exact
    sb = masker.seal(None, "a", 4, roster, 1234)
    assert sb.kind == "bytes" and sb.payload is None and sb.nbytes == 1234


def test_apply_mask_flat_inverse(spec):
    rng = np.random.default_rng(3)
    buf = jnp.asarray(rng.standard_normal(spec.n), jnp.float32)
    seeds = np.asarray(rng.integers(0, 2**32, 4), np.uint32)
    signs = np.asarray([1, -1, 1, -1], np.int32)
    y = apply_mask_flat(buf, seeds, signs)
    assert not np.array_equal(np.asarray(y), np.asarray(buf))
    back = apply_mask_flat(y, seeds, -signs)
    assert np.array_equal(np.asarray(back), np.asarray(buf))


# ------------------------------------------------------------------ kernels


@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_masked_aggregate_bit_identical_to_plain(spec, quantize, use_kernel):
    """The acceptance invariant: when every sender survives, the fused
    unmask-aggregate path returns bit-identical mean / int8 codes /
    scales to the plain kernels."""
    masker = PairwiseMasker(0)
    models, sealed, secrets = _sealed(spec, masker)
    weights = list(np.random.default_rng(1).random(len(models)) + 0.1)
    seeds, signs = masker.unmask_matrices(sealed, secrets)
    kw = dict(spec=spec, quantize=quantize, use_kernel=use_kernel,
              interpret=use_kernel or None)
    plain = aggregate_flatmodel(list(models), weights, **kw)
    masked = masked_aggregate_flatmodel([sm.payload for sm in sealed],
                                        weights, seeds=seeds, signs=signs,
                                        **kw)
    if quantize:
        assert np.array_equal(np.asarray(plain[0].buffer),
                              np.asarray(masked[0].buffer))
        assert np.array_equal(np.asarray(plain[1]), np.asarray(masked[1]))
        assert np.array_equal(np.asarray(plain[2]), np.asarray(masked[2]))
    else:
        assert np.array_equal(np.asarray(plain.buffer),
                              np.asarray(masked.buffer))


@pytest.mark.parametrize("quantize", [False, True])
def test_masked_rows_reach_the_kernel_as_uint32(spec, quantize, monkeypatch):
    """Sealed words include NaN bit patterns, which an fp32 copy on the
    TPU need not keep (a v5e rewrote 2141 of 273,344 words of an fp32
    stack): the aggregator must stack and hand them over as uint32."""
    import repro.kernels.ops as ops

    name = ("unmask_aggregate_quantize_flat" if quantize
            else "unmask_aggregate_flat")
    real = getattr(ops, name)
    seen = []

    def spy(y, *a, **k):
        seen.append(y.dtype)
        return real(y, *a, **k)

    monkeypatch.setattr(ops, name, spy)
    masker = PairwiseMasker(0)
    _, sealed, secrets = _sealed(spec, masker)
    seeds, signs = masker.unmask_matrices(sealed, secrets)
    masked_aggregate_flatmodel([sm.payload for sm in sealed], seeds=seeds,
                               signs=signs, spec=spec, quantize=quantize,
                               use_kernel=True, interpret=True)
    assert seen == [jnp.uint32]


def test_masked_aggregate_sharded_dispatch_bit_identical(spec):
    """Sharded path on a 1×1 mesh (buildable anywhere): same bits as the
    unsharded plain path. The CI sharded job and sharded_child.py rerun
    this with 8 real shards."""
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    fs = spec.sharding(mesh)
    masker = PairwiseMasker(0)
    models, sealed, secrets = _sealed(spec, masker)
    seeds, signs = masker.unmask_matrices(sealed, secrets)
    plain = aggregate_flatmodel(list(models), spec=spec, quantize=True)
    masked = masked_aggregate_flatmodel([sm.payload for sm in sealed],
                                        seeds=seeds, signs=signs, spec=spec,
                                        quantize=True, shardings=fs)
    assert np.array_equal(np.asarray(plain[0].buffer),
                          np.asarray(masked[0].buffer))
    assert np.array_equal(np.asarray(plain[1]), np.asarray(masked[1]))
    assert np.array_equal(np.asarray(plain[2]), np.asarray(masked[2]))


# ----------------------------------------------------------------- protocol


def _sniff(session):
    """Wrap Network.send; record any model payload that is not sealed."""
    leaks, counts = [], {}
    orig = session.net.send

    def send(src, dst, msg):
        name = type(msg).__name__
        counts[name] = counts.get(name, 0) + 1
        model = getattr(msg, "model", None)
        if model is not None and name in ("AggregateMsg", "MaskedModelMsg"):
            if name == "AggregateMsg" or not isinstance(model.params,
                                                        SealedModel):
                leaks.append((src, dst, name))
        orig(src, dst, msg)

    session.net.send = send
    return leaks, counts


def test_secure_session_progresses_with_threshold_gate():
    s = ModestSession(n_nodes=20, mcfg=MCFG, tcfg=TrainConfig(),
                      task=TASK, seed=0)
    leaks, counts = _sniff(s)
    res = s.run(120.0)
    assert res.rounds_completed > 20
    assert leaks == [], leaks[:5]              # nothing plaintext, ever
    # the recovery machinery really ran
    assert counts.get("MaskedModelMsg", 0) > 0
    assert counts.get("ShareMsg", 0) > 0
    assert counts.get("UnmaskShareMsg", 0) > 0
    logs = [e for n in s.nodes.values() for e in n.secagg_log]
    assert logs
    for k, t, n_sealed, margin in logs:
        assert margin >= 0, (k, t, margin)     # never below threshold
        assert n_sealed >= 1
    # share/recovery traffic is visible in the byte accounting
    usage = s.net.usage_summary()
    for kind in ("ShareMsg", "MaskedModelMsg", "UnmaskReq", "UnmaskShareMsg"):
        assert usage["by_type"].get(kind, 0) > 0, kind


def test_plain_config_pays_zero_secure_cost():
    mcfg = dataclasses.replace(MCFG, secure_agg=None)
    s = ModestSession(n_nodes=20, mcfg=mcfg, tcfg=TrainConfig(),
                      task=TASK, seed=0)
    res = s.run(60.0)
    assert res.rounds_completed > 10
    for kind in ("ShareMsg", "MaskedModelMsg", "UnmaskReq", "UnmaskShareMsg"):
        assert s.net.msgs_by_type.get(kind, 0) == 0
    assert all(n._masker is None for n in s.nodes.values())
    # the roster slot is free when empty: TrainMsg wire size is unchanged
    a = M.TrainMsg(sender="0", round_k=1, model=M.ModelPayload(nbytes=100))
    b = M.TrainMsg(sender="0", round_k=1, model=M.ModelPayload(nbytes=100),
                   roster=())
    assert a.size_bytes() == b.size_bytes()


def _bare_secure_node():
    mcfg = ModestConfig(n_nodes=4, sample_size=2, n_aggregators=1,
                        success_fraction=1.0, ping_timeout=1.0,
                        secure_agg="masked")
    sim = Simulator()
    net = Network(sim, 4)
    node = ModestNode("0", sim, net, mcfg, TrainConfig(),
                      AbstractTask(model_bytes_=1000))
    node.bootstrap(["0", "1", "2", "3"])
    return sim, net, node


def test_aggregator_never_unmasks_below_threshold():
    """Deterministic threshold-gate check: sealed models arrive but the
    roster's shares never do — the aggregator must abort (bounded
    re-polls), never aggregate; late shares then complete the round."""
    sim, net, node = _bare_secure_node()
    masker = PairwiseMasker(0)                 # same session seed
    roster = ("1", "2", "3")
    k_train, k_agg = 4, 5
    for sender in ("1", "2"):
        sm = masker.seal(None, sender, k_train, roster, 1000)
        node.receive(M.MaskedModelMsg(
            sender=sender, round_k=k_agg,
            model=M.ModelPayload(params=sm, nbytes=1000), roster=roster))
    assert k_agg not in node._agg_models_done  # gate holds immediately
    sim.run(until=node.SA_UNMASK_TIMEOUT_MULT * node.timeout
            * (node.SA_MAX_TRIES + 1))
    assert k_agg not in node._agg_models_done  # still sealed after retries
    assert node.secagg_aborts >= 1
    assert node.secagg_log == []
    # now the shares arrive (t = threshold(3) = 3 per sender): the round
    # becomes recoverable and completes via the sf/stall machinery
    node._sa_pending.add(k_agg)                # re-open collection window
    for owner in ("1", "2"):
        for member, share in masker.make_shares(owner, k_train,
                                                roster).items():
            node.receive(M.UnmaskShareMsg(
                sender=member, round_k=k_train,
                shares=((owner, share[0], share[1]),)))
    assert k_agg in node._agg_models_done
    assert len(node.secagg_log) == 1
    k, t, n_sealed, margin = node.secagg_log[0]
    assert (k, t, n_sealed) == (k_agg, 3, 2) and margin >= 0


def test_mixed_scalar_rows_unseal_exactly():
    """Cold path: scalar-sealed rows (AbstractTask params) mixed with a
    plain row unseal per-row and aggregate to the exact plain mean."""
    _, _, node = _bare_secure_node()
    masker = node._masker
    roster = ("1", "2")
    vals = {"1": np.float32(1.5), "2": np.float32(2.5)}
    models = [M.ModelPayload(params=masker.seal(vals[s], s, 3, roster, 4))
              for s in roster]
    models.append(M.ModelPayload(params=np.float32(3.0)))   # plain row
    secrets = {s: masker.secret(s, 3) for s in roster}
    out = node._sa_aggregate(models, secrets)
    assert out.params == np.mean([1.5, 2.5, 3.0]).astype(np.float32)
