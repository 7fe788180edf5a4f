"""Operation and byte counts from shapes, kept with the benchmark.

``train_flops_per_sample`` counts the multiply-adds of one forward pass
(2 FLOPs each) and takes the backward pass as twice the forward, the
usual 3x for training. A family's forward count lives in a file of its
own, ``counts/<family>.py``, whose ``forward_macs(model)`` this module
finds by the family's name; an unknown family is an error that names the
missing file. ``aggregation_bytes`` is the HBM traffic the aggregation
algorithm needs for one call, at the unpadded width N, as
``repro.roofline.aggregation_roofline`` models the one-pass kernel.
"""

from __future__ import annotations

import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = os.path.join(HERE, "peaks.json")


def forward_flops_per_sample(model: dict, base: str = HERE) -> int:
    from harness import load_module

    return 2 * load_module("counts", model["family"], base).forward_macs(
        model)


def train_flops_per_sample(model: dict, base: str = HERE) -> int:
    return 3 * forward_flops_per_sample(model, base)


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
