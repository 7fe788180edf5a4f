"""Operation and byte counts, and the peaks table, against hand counts."""

import math

import pytest

import counting
import harness


LENET = {"family": "cnn", "image": [32, 32, 3], "channels": [6, 16],
         "kernel": 5, "pool": 2, "hidden": [120, 84], "classes": 10}


def test_lenet_flops_match_a_hand_count():
    conv1 = 32 * 32 * 5 * 5 * 3 * 6          # 460,800 multiply-adds
    conv2 = 16 * 16 * 5 * 5 * 6 * 16         # 614,400
    dense = 8 * 8 * 16 * 120 + 120 * 84 + 84 * 10
    forward = 2 * (conv1 + conv2 + dense)
    assert counting.forward_flops_per_sample(LENET) == forward == 2_418_000
    assert counting.train_flops_per_sample(LENET) == 3 * forward


@pytest.mark.parametrize("name", [c["name"] for c in
                                  harness.manifest()["configs"]])
def test_config_files_state_their_counts(name):
    config = harness.load_json("configs", name)
    assert config["train_flops_per_sample"] == \
        counting.train_flops_per_sample(config["model"])
    assert config["params"] == harness.build_task(config).flat_spec.n


def test_an_unknown_family_is_an_error_naming_its_file():
    with pytest.raises(KeyError, match=r"counts/no-such-family\.py"):
        counting.train_flops_per_sample({"family": "no-such-family"})


@pytest.mark.parametrize("p", [1, 2, 8, 10])
def test_aggregation_bytes(p):
    n = 136_672
    assert counting.aggregation_bytes(n, p) == (p + 1) * n * 4
    assert counting.aggregation_bytes(n, p, quantize=True) == \
        (p + 1) * n * 4 + n + 4 * math.ceil(n / 16384)


def test_peaks_are_keyed_by_device_kind_with_a_source():
    v5e = counting.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        counting.peaks("cpu")
