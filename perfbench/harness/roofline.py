"""Chip peaks and the least bytes the FD sweep has to move.

``peaks.json`` (beside the harness) holds each chip's published peaks,
keyed by ``device_kind`` as JAX reports it; a chip that is not there is
an error, never a default.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def sweep_least_bytes(entries: int, n: int, k: int, *, churn: bool,
                      strategy1: bool, float_bytes: int = 8,
                      owner_bytes: int = 4) -> int:
    """Bytes one FD sweep over ``entries`` queries of ``n`` peers must
    move through device memory, whatever computes it.

    In, once per entry and peer: the k local scores, the execution time
    and the up and down link terms, the death time under churn and the
    Strategy-1 wait with Strategy 1.  Out, once per entry and peer: the
    merged k-list (k scores and k owners), the send and list-arrival
    times, and the liveness flag under churn.  Everything in between can
    stay on chip in principle, so it is not counted.
    """
    per_peer_in = k * float_bytes + 3 * float_bytes
    per_peer_in += float_bytes * (int(churn) + int(strategy1))
    per_peer_out = k * (float_bytes + owner_bytes) + 2 * float_bytes
    per_peer_out += int(churn)
    return entries * n * (per_peer_in + per_peer_out)
