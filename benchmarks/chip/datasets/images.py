"""CIFAR-10-shaped image classification data, generated from a seed.

A copy of the Gaussian-cluster generator of ``repro.data.synthetic``
(``make_classification_task``), kept with the benchmark so that no later
change to the program moves the yardstick. Class ``c`` has a random mean
image; a sample is its class mean plus Gaussian noise. The training set is
split IID into equal shards, one per node.
"""

from __future__ import annotations

import numpy as np


def make(spec: dict, nodes: int, seed: int) -> dict:
    """``{"clients": [(x, y), ...], "test": (x, y)}`` as numpy arrays."""
    rng = np.random.default_rng([seed, 11])
    shape = tuple(spec["image"])
    classes = spec["classes"]
    n_train, n_test = spec["train"], spec["test"]
    if n_train % nodes:
        raise ValueError(f"{n_train} images do not split evenly over "
                         f"{nodes} nodes")
    means = rng.normal(0.0, spec["mean_std"],
                       size=(classes,) + shape).astype(np.float32)

    def draw(n):
        labels = rng.integers(0, classes, size=n)
        x = rng.standard_normal(size=(n,) + shape, dtype=np.float32)
        x *= np.float32(spec["noise_std"])
        x += means[labels]
        return x, labels.astype(np.int64)

    x, y = draw(n_train)
    order = rng.permutation(n_train)
    clients = [(x[np.sort(part)], y[np.sort(part)])
               for part in np.array_split(order, nodes)]
    return {"clients": clients, "test": draw(n_test)}
