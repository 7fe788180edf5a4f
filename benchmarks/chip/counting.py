"""Operation and byte counts from shapes, kept with the benchmark.

``train_flops_per_sample`` counts the multiply-adds of one forward pass
(2 FLOPs each) and takes the backward pass as twice the forward, the
usual 3x for training. Element-wise work (bias, ReLU, pooling, softmax)
is left out. ``aggregation_bytes`` is the HBM traffic the aggregation
algorithm needs for one call, at the unpadded width N, as
``repro.roofline.aggregation_roofline`` models the one-pass kernel.
"""

from __future__ import annotations

import json
import math
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def _cnn_macs(m: dict) -> int:
    h, w, c = m["image"]
    k = m["kernel"]
    macs = 0
    for c_out in m["channels"]:
        macs += h * w * k * k * c * c_out          # "same" convolution
        h, w, c = h // m["pool"], w // m["pool"], c_out
    width = h * w * c
    for d in list(m["hidden"]) + [m["classes"]]:
        macs += width * d
        width = d
    return macs


_MACS = {"cnn": _cnn_macs}


def forward_flops_per_sample(model: dict) -> int:
    return 2 * _MACS[model["family"]](model)


def train_flops_per_sample(model: dict) -> int:
    return 3 * forward_flops_per_sample(model)


def aggregation_bytes(n: int, p: int, *, itemsize: int = 4,
                      quantize: bool = False) -> int:
    """Bytes one aggregation of ``p`` models of ``n`` parameters moves:
    one-pass reads the (P, N) stack and writes the mean, ``(P+1)·N·4``;
    quantize adds the int8 codes and one fp32 scale per 16384 lanes;
    masked reads ``P·N·4`` and writes ``N·4``, the same count."""
    total = (p + 1) * n * itemsize
    if quantize:
        total += n + 4 * math.ceil(n / 16384)
    return total


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(table)})")
    return table[device_kind]
