"""The diurnal population of ``repro.traces.generators.diurnal_profile``,
copied with its WAN latency matrix (``repro.sim.network.wan_latency_matrix``)
so that no later change to the program moves the yardstick.

Heavy-tailed (lognormal) seconds per batch, asymmetric last-mile links,
one-way WAN latency between synthetic cities with round-robin node-to-city
assignment, and one online window per ``period`` per node. The multisets of
speeds, links, windows and cities come from ``base_seed`` and are the same
for every seed; the seed deals them to the nodes in another order, so every
seed sees the same population in aggregate.
"""

from __future__ import annotations

import numpy as np


def wan_latency_matrix(n_cities: int, rng) -> np.ndarray:
    """One-way latency in seconds between cities on a sphere: up to 100 ms
    of propagation, 2-20 ms of jitter per pair, 0.5 ms within a city."""
    v = rng.normal(size=(n_cities, 3))
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    ang = np.arccos(np.clip(v @ v.T, -1, 1))
    base = ang / np.pi * 0.100
    jitter = rng.uniform(0.002, 0.02, size=(n_cities, n_cities))
    lat = base + (jitter + jitter.T) / 2
    np.fill_diagonal(lat, 0.0005)
    return lat.astype(np.float64)


def _windows(n: int, rng, p: dict) -> list:
    """One ``[start, start + f·period)`` window per node, split in two
    where it wraps the period boundary."""
    period = p["period"]
    common = rng.uniform(0.0, period)
    out = []
    for _ in range(n):
        frac = float(np.clip(rng.normal(p["mean_availability"],
                                        p["availability_jitter"]),
                             0.15, 0.98))
        phase = float(rng.uniform(0.0, period))
        c = p["phase_concentration"]
        start = (c * common + (1.0 - c) * phase) % period
        end = start + frac * period
        out.append(((start, end),) if end <= period
                   else ((0.0, end - period), (start, period)))
    return out


def make(p: dict, n: int, seed: int) -> dict:
    """Per-node arrays and availability windows (periodic, ``period``)."""
    base = np.random.default_rng(p["base_seed"])
    lat = wan_latency_matrix(min(p["cities"], max(n, 2)), base)
    speeds = p["base_speed"] * base.lognormal(0.0, p["speed_sigma"], size=n)
    speeds = np.clip(speeds, p["base_speed"] / p["speed_cap"],
                     p["base_speed"] * p["speed_cap"])
    down = p["downlink_median"] * base.lognormal(0.0, p["bandwidth_sigma"],
                                                 size=n)
    ratio = p["asymmetry_median"] * base.lognormal(0.0, 0.3, size=n)
    up = down / np.maximum(ratio, 1.0)
    windows = _windows(n, base, p)
    city = np.arange(n) % len(lat)

    deal = np.random.default_rng([seed, 13])
    order = [deal.permutation(n) for _ in range(4)]
    return {"speeds": speeds[order[0]], "uplink": up[order[1]],
            "downlink": down[order[1]], "latency": lat,
            "city": city[order[2]],
            "windows": [windows[i] for i in order[3]],
            "period": p["period"]}
