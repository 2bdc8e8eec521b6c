"""The L7 stage's work, counted from what it decides and not from how
the program lays its tables out: for each tuple whose request the L7
stage decides (every redirected tuple), the request's field bytes at
their actual lengths (HTTP method, path and host; Kafka api key,
version and client id at 4 bytes each and 4 bytes per topic), 8 bytes
per header of a name that some rule names (a name id and a value id),
and 4 bytes per L7 rule of its scope, (endpoint, port), as the policy
states it.
l7_roofline divides it by the L7 program's device time and the HBM
peak of the chip (`PEAK_HBM_BYTES_PER_S`)."""

from __future__ import annotations

from typing import Optional

import numpy as np

# Google Cloud documentation, "TPU v5e": 819 GB/s of HBM bandwidth per
# chip; keyed by jax's device_kind
PEAK_HBM_BYTES_PER_S = {"TPU v5 lite": 819e9, "TPU v5e": 819e9}


def peak_bytes_per_s(device_kind: str) -> Optional[float]:
    return PEAK_HBM_BYTES_PER_S.get(device_kind)


def request_bytes(world) -> np.ndarray:
    """Bytes of the L7 stage's work per (pool row, request), rows
    flattened as row * n + j; computed once per world."""
    got = getattr(world, "l7_request_bytes", None)
    if got is not None:
        return got
    scope_rules = {}
    named = {h.split(" ", 1)[0].rstrip(":").lower()
             for r in world.l7_rules for h in r.get("headers", ())}
    for r in world.l7_rules:
        key = (r["app"], r["port"])
        scope_rules[key] = scope_rules.get(key, 0) + 1
    app_of_axis = {int(axis): ep - 100 for ep, axis in world.index.items()}
    pool = world.pool
    out = []
    for row, reqs in enumerate(world.requests):
        app = app_of_axis[int(pool["ep_index"][row])]
        rules = 4 * scope_rules.get((app, int(pool["dport"][row])), 0)
        for method, path, host, headers, kafka in reqs:
            if kafka is None:
                b = len(method) + len(path) + len(host) + 8 * sum(
                    name.lower() in named for name, _ in headers)
            else:
                b = 12 + 4 * len(kafka[3])
            out.append(b + rules)
    world.l7_request_bytes = np.asarray(out, np.float64)
    return world.l7_request_bytes


def launch_bytes(world, decided: np.ndarray, weights) -> float:
    """The work of one launch whose tuples carry (pool row, request)
    x weights[0][x] + weights[1][x] times; `decided` marks the
    requests the L7 stage decides."""
    w = np.asarray(weights[0], np.float64) + np.asarray(weights[1])
    return float((w * request_bytes(world))[decided].sum())
