"""A Gnutella-like overlay (``family: "gnutella"``): a BA core with
host-cache shortcuts, as the program's generator makes it, except for
how fragments reconnect."""
import numpy as np

from harness.overlay import Overlay, _ba_adj, _rejoin


def build(n: int, seed: int, m: int = 2,
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
