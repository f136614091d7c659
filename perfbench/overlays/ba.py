"""BRITE's flat Barabasi-Albert model (``family: "ba"``), a copy of the
program's generator: the same construction and random stream."""
import numpy as np

from harness.overlay import Overlay, _ba_adj


def build(n: int, seed: int, m: int = 2) -> Overlay:
    """BRITE's flat BA model: m edges per joining peer (d(G) about 2m)."""
    adj = _ba_adj(n, m, np.random.default_rng(seed))
    return Overlay("ba", [np.array(sorted(a), np.int32) for a in adj])
