"""Multiply-adds of one forward pass of the paper's LeNet-style CNN, from
its config: "same" convolutions, each followed by a pool, then dense
layers. Element-wise work (bias, ReLU, pooling, softmax) is left out."""

from __future__ import annotations


def forward_macs(m: dict) -> int:
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
