"""The benchmark's copied generators: the same seed gives the same
arrays, and every seed gets the same multisets of sizes in another order."""

import numpy as np
import pytest

import harness
import tiny


def _arrays(cell):
    out = [cell.test[0], cell.test[1]]
    for x, y in cell.shards:
        out += [x, y]
    p = cell.profile
    return out + [p.speeds, p.uplink, p.downlink, p.latency, p.city]


@pytest.mark.parametrize("name", [w["name"] for w in
                                  harness.manifest()["workloads"]])
def test_same_seed_same_inputs(name):
    config, traffic = tiny.tiny(name)
    a = harness.build_cell(name, config, traffic, 2**31 + 12345)
    b = harness.build_cell(name, config, traffic, 2**31 + 12345,
                           task=a.task)
    for u, v in zip(_arrays(a), _arrays(b)):
        np.testing.assert_array_equal(u, v)
    assert [tl.intervals for tl in a.profile.availability] == \
        [tl.intervals for tl in b.profile.availability]
    c = harness.build_cell(name, config, traffic, 7, task=a.task)
    assert any(u.shape != v.shape or not np.array_equal(u, v)
               for u, v in zip(_arrays(a)[:2], _arrays(c)[:2]))


def test_seeds_deal_one_population():
    config, traffic = tiny.tiny("cnn-modest-diurnal")
    p = [harness.build_profile(traffic, s) for s in (1, 2)]
    assert not np.array_equal(p[0].speeds, p[1].speeds)
    for attr in ("speeds", "uplink", "downlink", "city"):
        np.testing.assert_array_equal(np.sort(getattr(p[0], attr)),
                                      np.sort(getattr(p[1], attr)))
    fractions = [sorted(tl.online_fraction() for tl in q.availability)
                 for q in p]
    np.testing.assert_allclose(fractions[0], fractions[1])


def test_images_split_evenly():
    config, traffic = tiny.tiny("cnn-modest-diurnal")
    ds = config["dataset"]
    g = harness.load_module("datasets", "images").make(ds, traffic["nodes"],
                                                       9)
    assert [len(x) for x, _ in g["clients"]] == \
        [ds["train"] // traffic["nodes"]] * traffic["nodes"]
    assert g["test"][0].shape == (ds["test"], *ds["image"])
    assert g["clients"][0][0].dtype == np.float32


def test_every_seed_runs_one_pool_in_its_own_order():
    orders = [harness.pass_order(s, 5) for s in (1, 2**31 + 9, 77)]
    for o in orders:
        assert sorted(o) == list(range(5))
    assert len({tuple(o) for o in orders}) > 1
    assert harness.pass_order(77, 5) == orders[2]


def test_a_pool_session_does_the_same_work_for_every_seed():
    """The protocol of a pool session comes from the traffic file, so two
    seeds (other data, other weights) give it the same rounds, jobs,
    flushes and aggregations."""
    config, traffic = tiny.tiny("cnn-modest-diurnal")
    a = harness.build_cell("cnn-modest-diurnal", config, traffic, 3)
    b = harness.build_cell("cnn-modest-diurnal", config, traffic,
                           2**31 + 5, task=a.task)
    sa, sb = (harness.run_session(c, 1, harness.Recorder(c.seed))
              for c in (a, b))
    assert sa.rounds > 0
    assert (sa.rounds, sa.jobs, sa.flushes, sa.agg_sizes, sa.events) == \
        (sb.rounds, sb.jobs, sb.flushes, sb.agg_sizes, sb.events)


TOKENS = {"kind": "tokens", "seq_len": 12, "vocab": 96, "per_node": 40,
          "test": 32, "topics": 4, "skew": 0.9}


def test_tokens_are_the_seeds():
    make = harness.load_module("datasets", "tokens").make
    a, b = make(TOKENS, 6, 2**31 + 5), make(TOKENS, 6, 2**31 + 5)
    c = make(TOKENS, 6, 2**31 + 6)
    for (x, y), (u, v) in zip(a["clients"] + [a["test"]],
                              b["clients"] + [b["test"]]):
        np.testing.assert_array_equal(x, u)
        np.testing.assert_array_equal(y, v)
    assert not np.array_equal(a["clients"][0][0], c["clients"][0][0])
    x, y = a["clients"][0]
    assert x.shape == y.shape == (40, 12) and x.dtype == np.int32
    assert a["test"][0].shape == (32, 12)
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])   # next tokens
    assert 0 <= x.min() and max(x.max(), y.max()) < TOKENS["vocab"]


def test_the_topic_skew_is_visible():
    """At skew 0.9 nine tenths of a node's tokens (and a little more: the
    rest fall in its band a quarter of the time) lie in its topic's band;
    at skew 0 a quarter do, as in any band."""
    make = harness.load_module("datasets", "tokens").make
    width = TOKENS["vocab"] // TOKENS["topics"]
    for skew, want in ((0.9, 0.9 + 0.1 / 4), (0.0, 1 / 4)):
        d = make(dict(TOKENS, skew=skew, per_node=200), 8, 11)
        for (x, _), topic in zip(d["clients"], d["node_topics"]):
            share = np.mean(x // width == topic)
            assert abs(share - want) < 0.05, (skew, topic, share)
    d = make(TOKENS, 8, 11)
    assert len(set(d["node_topics"].tolist())) > 1
