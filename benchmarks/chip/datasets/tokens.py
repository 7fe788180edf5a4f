"""Token sequences for a language-model cell, generated from a seed.

Each of ``nodes`` nodes holds ``per_node`` sequences, and the test set
``test`` sequences, of ``seq_len`` input ids and their ``seq_len``
next-token labels, over a vocabulary (slice) of ``vocab`` ids. The slice
is cut into ``topics`` equal bands, and each node is given one band,
drawn from the seed. A token of a node's sequence comes from its band
with probability ``skew`` and from the whole slice otherwise, uniformly
in either case; a test sequence takes a band drawn anew. At ``skew`` > 0
the nodes of a cohort feed different embeddings to a router, so an
MoE's experts see uneven load; at 0 every node draws alike.

Every parameter comes from the config, and none has a default. No source
backs a band split or a skew yet: a config that uses this generator names
the public source of its ``seq_len``, ``topics`` and ``skew``.
"""

from __future__ import annotations

import numpy as np


def _draw(rng, bands: np.ndarray, spec: dict) -> tuple:
    """``(x, y)`` of one sequence per entry of ``bands``."""
    n, length = len(bands), spec["seq_len"] + 1
    vocab, topics = spec["vocab"], spec["topics"]
    width = vocab // topics
    own = bands[:, None] * width + rng.integers(0, width, size=(n, length))
    anywhere = rng.integers(0, vocab, size=(n, length))
    tokens = np.where(rng.random((n, length)) < spec["skew"], own,
                      anywhere).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def make(spec: dict, nodes: int, seed: int) -> dict:
    """``{"clients": [(x, y), ...], "test": (x, y)}`` as int32 arrays of
    shape ``(sequences, seq_len)``; ``node_topics`` gives each node's
    band."""
    if spec["vocab"] < spec["topics"]:
        raise ValueError(f"{spec['topics']} topics do not fit a vocabulary "
                         f"of {spec['vocab']}")
    rng = np.random.default_rng([seed, 13])
    topics = rng.integers(0, spec["topics"], size=nodes)
    clients = [_draw(rng, np.full(spec["per_node"], t), spec)
               for t in topics]
    test = _draw(rng, rng.integers(0, spec["topics"], size=spec["test"]),
                 spec)
    return {"clients": clients, "test": test, "node_topics": topics}
