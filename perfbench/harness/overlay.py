"""The overlay a configuration names, built from its topology seed.

The benchmark makes its own data.  A configuration's ``overlay`` block
names its generator by ``family``: ``perfbench/overlays/<family>.py``,
whose ``build(peers, topology_seed, **family_args)`` returns an
``Overlay``; a new deployment brings its generator as a new file.  The
generators there are copies of the program's (the same construction
and random streams; ``gnutella`` differs in how it reconnects
fragments), so the overlay handed to the system under test and the one
the reference floods are the benchmark's, not the program's.  This
module holds what they share.  A configuration pins the result by its
edge count and the degree of each fixed origin, and ``build`` refuses
an overlay that does not match.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from harness import bench


@dataclasses.dataclass
class Overlay:
    """Adjacency lists (sorted int32) plus optional plane coordinates."""

    kind: str
    neighbors: List[np.ndarray]
    coords: Optional[np.ndarray] = None

    @property
    def n(self) -> int:
        return len(self.neighbors)

    @property
    def n_edges(self) -> int:
        return sum(len(a) for a in self.neighbors) // 2

    def degree(self) -> np.ndarray:
        return np.array([len(a) for a in self.neighbors])


def _ba_adj(n: int, m: int, rng: np.random.Generator) -> List[set]:
    """Barabasi-Albert preferential attachment from an (m+1)-clique."""
    adj: List[set] = [set() for _ in range(n)]
    core = min(m + 1, n)
    for u in range(core):
        for v in range(u + 1, core):
            adj[u].add(v)
            adj[v].add(u)
    targets = []            # every edge endpoint: degree-proportional draw
    for u in range(core):
        targets.extend([u] * len(adj[u]))
    for u in range(core, n):
        chosen: set = set()
        while len(chosen) < min(m, u):
            cand = int(targets[rng.integers(len(targets))])
            if cand != u:
                chosen.add(cand)
        for v in chosen:
            adj[u].add(v)
            adj[v].add(u)
            targets.extend([u, v])
    return adj


def _components(adj: List[set]) -> np.ndarray:
    n = len(adj)
    comp = -np.ones(n, dtype=np.int64)
    cur = 0
    for s in range(n):
        if comp[s] >= 0:
            continue
        stack = [s]
        comp[s] = cur
        while stack:
            u = stack.pop()
            for v in adj[u]:
                if comp[v] < 0:
                    comp[v] = cur
                    stack.append(v)
        cur += 1
    return comp


def _rejoin(adj: List[set], rng: np.random.Generator) -> None:
    """Link each component other than the largest, by its lowest peer,
    to a uniform peer of the largest: a servent cut off by rewiring
    bootstraps again from its host cache."""
    comp = _components(adj)
    sizes = np.bincount(comp)
    giant = np.flatnonzero(comp == int(np.argmax(sizes)))
    for c in range(len(sizes)):
        if c == int(np.argmax(sizes)):
            continue
        a = int(np.flatnonzero(comp == c)[0])
        b = int(giant[rng.integers(len(giant))])
        adj[a].add(b)
        adj[b].add(a)


def build(overlay_cfg: dict, check: bool = True,
          root: str = bench.CHECKOUT) -> Overlay:
    """The overlay of a configuration's ``overlay`` block, made by the
    generator its ``family`` names under ``root``.

    ``check`` holds it to the block's ``edges`` and to the median degree
    at every fixed origin, so a generator that drifted cannot go unseen.
    """
    gen = bench.module("overlays", overlay_cfg["family"], root).build
    ov = gen(int(overlay_cfg["peers"]), int(overlay_cfg["topology_seed"]),
             **overlay_cfg.get("family_args", {}))
    if check:
        if ov.n_edges != overlay_cfg["edges"]:
            raise ValueError(f"overlay has {ov.n_edges} edges, the "
                             f"configuration states {overlay_cfg['edges']}")
        deg = ov.degree()
        med = int(np.median(deg))
        for o in overlay_cfg["origins"]:
            if deg[o] != med:
                raise ValueError(f"origin {o} has degree {deg[o]}, not "
                                 f"the median {med}")
    return ov


def pick_origins(ov: Overlay, count: int, seed: int) -> list:
    """``count`` peers of the median degree, drawn from ``seed``: the
    ordinary peers most queries come from, not hubs."""
    deg = ov.degree()
    cand = np.flatnonzero(deg == int(np.median(deg)))
    rng = np.random.default_rng(seed)
    return sorted(int(x) for x in rng.choice(cand, count, replace=False))
