"""The overlay a configuration names, built from its topology seed.

The benchmark makes its own data: these generators are copies of the
program's BRITE-style BA and Gnutella-like generators (the same
construction and random streams; ``gnutella`` differs in how it
reconnects fragments), so the overlay handed to the system under test
and the one the reference floods are the benchmark's, not the
program's.  A configuration pins the result by its edge count and the
degree of each fixed origin, and ``build`` refuses an overlay that does
not match.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


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


def ba(n: int, seed: int, m: int = 2) -> Overlay:
    """BRITE's flat BA model: m edges per joining peer (d(G) about 2m)."""
    adj = _ba_adj(n, m, np.random.default_rng(seed))
    return Overlay("ba", [np.array(sorted(a), np.int32) for a in adj])


def gnutella(n: int, seed: int, m: int = 2,
             rewire_p: float = 0.10) -> Overlay:
    """A BA core whose edges are each re-pointed, with ``rewire_p``,
    from the higher endpoint to a uniform peer (host-cache shortcuts);
    a rewire that would make a self-loop or a duplicate keeps the edge.
    Coordinates are uniform in the unit square.

    Rewiring cuts off some hundreds of low-degree peers.  The program's
    own ``gnutella`` generator chains those fragments into one path
    (``_bridge_chain``), which gives a 400-hop flood tree at 100k peers;
    here each fragment rejoins the largest component instead
    (``_rejoin``), as a real servent would."""
    rng = np.random.default_rng(seed)
    adj = _ba_adj(n, m, rng)
    coords = rng.random((n, 2))
    edges = [(u, int(v)) for u in range(n) for v in adj[u] if u < v]
    flips = rng.random(len(edges)) < rewire_p
    targets = rng.integers(0, n, len(edges))
    for (u, v), flip, w in zip(edges, flips, targets):
        w = int(w)
        if not flip or w == u or w in adj[u] or v not in adj[u]:
            continue
        adj[u].discard(v)
        adj[v].discard(u)
        adj[u].add(w)
        adj[w].add(u)
    _rejoin(adj, rng)
    return Overlay("gnutella", [np.array(sorted(a), np.int32) for a in adj],
                   coords)


FAMILIES = {"ba": ba, "gnutella": gnutella}


def build(overlay_cfg: dict, check: bool = True) -> Overlay:
    """The overlay of a configuration's ``overlay`` block.

    ``check`` holds it to the block's ``edges`` and to the median degree
    at every fixed origin, so a generator that drifted cannot go unseen.
    """
    fam = overlay_cfg["family"]
    if fam not in FAMILIES:
        raise ValueError(f"unknown overlay family {fam!r}; known: "
                         f"{sorted(FAMILIES)}")
    ov = FAMILIES[fam](int(overlay_cfg["peers"]),
                       int(overlay_cfg["topology_seed"]),
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
