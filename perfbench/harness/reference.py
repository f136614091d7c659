"""Plain reference of one FD top-k query: the scalar event simulator.

A copy of the paper's model as the repository first wrote it down
(Akbarinia, Pacitti and Valduriez, sections 3-5 and Appendix A), one
peer at a time in plain Python and numpy, and importing nothing of the
system under test.  It draws every random input from the query's own
seed in a fixed order: tuple counts, score uniforms, upward links,
downward links, deaths (under churn), item sizes, the Strategy-1 waits,
and finally the retrieval links; so the same (overlay, origin, seed,
parameters) give the same answer here and in any implementation that
keeps to that order.

Scope: the FD family (``basic``, ``st1``, ``st1+2`` forwarding, with or
without the section-4 urgent lists and rerouting), i.i.d. link
latencies (Table 1) and no replication.  A configuration outside that
scope is refused rather than answered wrongly.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

ENTRY_BYTES = 10       # one (score, address) couple in a k-list, 3.2
QUERY_BYTES = 100      # forward message: Q + QID + TTL + address


@dataclasses.dataclass(frozen=True)
class Params:
    """Table 1 of the paper, plus the Appendix-A wait-time estimates."""

    k: int = 20
    ttl: int = 0                      # 0: the origin's eccentricity
    latency_mean_s: float = 0.200
    latency_var: float = 0.100 ** 2
    bw_mean_Bps: float = 56_000.0 / 8.0
    bw_var: float = (32_000.0 / 8.0) ** 2
    tuples_lo: int = 1000
    tuples_hi: int = 20000
    item_mean_B: float = 1024.0
    item_std_B: float = 256.0
    exec_s_per_tuple: float = 2e-5
    merge_s: float = 0.002
    lam_max_s: float = 0.05
    request_B: int = 50
    t_qsnd_s: float = 0.5
    t_exec_max_s: float = 0.5
    t_slsnd_s: float = 0.5


@dataclasses.dataclass
class Answer:
    """What one query returns: the final top-k and its traffic."""

    values: np.ndarray            # (k,) descending scores at the origin
    owners: np.ndarray            # (k,) peer holding each score
    n_reached: int
    m_fw: int
    b_fw: int
    m_bw: int
    b_bw: int
    m_rt: int
    b_rt: int
    response_time_s: float
    accuracy: float


def bfs(neighbors, origin: int, ttl: int):
    """(parent, depth, reached) of the flood from ``origin``."""
    n = len(neighbors)
    parent = -np.ones(n, dtype=np.int64)
    depth = -np.ones(n, dtype=np.int64)
    depth[origin] = 0
    frontier = [origin]
    lvl = 0
    while frontier and lvl < ttl:
        nxt = []
        for u in frontier:
            for v in neighbors[u]:
                if depth[v] < 0:
                    depth[v] = lvl + 1
                    parent[v] = u
                    nxt.append(int(v))
        frontier = nxt
        lvl += 1
    return parent, depth, depth >= 0


def local_topk(n_tuples: np.ndarray, k: int, rng) -> np.ndarray:
    """(P, k) descending top-k of n_i U[0,1] scores by order statistics:
    top-1 = U^(1/n), each next one a further U^(1/remaining)."""
    u = rng.random((len(n_tuples), k))
    out = np.empty((len(n_tuples), k))
    cur = np.ones(len(n_tuples))
    remaining = n_tuples.astype(np.float64)
    for j in range(k):
        cur = cur * u[:, j] ** (1.0 / np.maximum(remaining, 1.0))
        out[:, j] = cur
        remaining -= 1.0
    return out


def wait_time(ttl_rem: np.ndarray, p: Params) -> np.ndarray:
    """Appendix A, formula (2): how long a peer waits for its children."""
    t = ttl_rem.astype(np.float64)
    return (t * p.t_qsnd_s + p.t_exec_max_s + t * p.t_slsnd_s
            + np.maximum(t - 1.0, 0.0) * p.merge_s)


def draw_link(rng, p: Params, size):
    lat = np.maximum(rng.normal(p.latency_mean_s,
                                math.sqrt(p.latency_var), size), 1e-3)
    bw = np.maximum(rng.normal(p.bw_mean_Bps, math.sqrt(p.bw_var), size),
                    1_000.0)
    return lat, bw


def forward_messages(neighbors, origin, parent, depth, reached, ttl,
                     strategy: str, p: Params, rng) -> int:
    """Messages of the forward phase under FD-Basic, Strategy 1 (random
    wait, each edge once w.h.p.) or Strategy 1+2 (piggybacked neighbor
    lists)."""
    n = len(neighbors)
    ttl_rem = ttl - depth
    if strategy == "basic":
        m = 0
        for u in range(n):
            if not reached[u] or ttl_rem[u] <= 0:
                continue
            deg = len(neighbors[u])
            m += deg if u == origin else deg - 1
        return m
    lam = rng.random(n) * p.lam_max_s
    send_at = np.where(depth >= 0, depth * p.t_qsnd_s, np.inf) + lam
    m = 0
    heard: dict = {}           # parent -> its neighbors and itself
    for u in range(n):
        if not reached[u] or ttl_rem[u] <= 0:
            continue
        pu = parent[u]
        plist: set = set()
        if strategy == "st1+2" and pu >= 0:
            if pu not in heard:
                heard[pu] = set(neighbors[pu].tolist()) | {int(pu)}
            plist = heard[pu]
        for v in neighbors[u]:
            v = int(v)
            if v == pu:
                continue
            if not reached[v]:
                m += 1              # a copy past the TTL still costs
                continue
            if strategy == "st1+2" and v in plist:
                continue            # Strategy 2: v has Q already
            if parent[v] == u:
                m += 1              # tree edge: u is v's first sender
            elif send_at[v] < send_at[u] and (parent[u] == v
                                              or depth[v] <= depth[u]):
                continue            # Strategy 1: u heard v's copy first
            else:
                m += 1
    return m


def fd_query(neighbors, origin: int, seed: int, p: Params, *,
             strategy: str = "st1+2", dynamic: bool = True,
             lifetime_mean_s: float = math.inf) -> Answer:
    """Simulate one FD top-k query from ``origin`` with seed ``seed``."""
    rng = np.random.default_rng(seed)
    n = len(neighbors)
    ttl = p.ttl
    if ttl == 0:
        parent, depth, reached = bfs(neighbors, origin, n)
        ttl = int(depth.max())
    else:
        parent, depth, reached = bfs(neighbors, origin, ttl)
    idx = np.flatnonzero(reached)
    ttl_rem = np.maximum(ttl - depth, 0)

    n_tuples = rng.integers(p.tuples_lo, p.tuples_hi + 1, n)
    scores = local_topk(n_tuples, p.k, rng)
    t_exec = n_tuples * p.exec_s_per_tuple
    lat_up, bw_up = draw_link(rng, p, n)      # v -> parent(v)
    lat_dn, bw_dn = draw_link(rng, p, n)      # parent(v) -> v

    t_q = np.full(n, np.inf)
    t_q[origin] = 0.0
    order = idx[np.argsort(depth[idx])]
    for v in order:
        if v != origin:
            t_q[v] = t_q[parent[v]] + (lat_dn[v] + QUERY_BYTES / bw_dn[v])
    t_ex_done = t_q + t_exec

    if math.isinf(lifetime_mean_s):
        death = np.full(n, np.inf)
    else:
        death = rng.exponential(lifetime_mean_s, n)
        death[origin] = np.inf

    list_bytes = p.k * ENTRY_BYTES
    rng.normal(p.item_mean_B, p.item_std_B, (n, p.k))   # item sizes
    m_fw = forward_messages(neighbors, origin, parent, depth, reached, ttl,
                            strategy, p, rng)
    m_bw = b_bw = 0

    deadline = t_q + wait_time(ttl_rem, p)
    children: list = [[] for _ in range(n)]
    for v in idx:
        if parent[v] >= 0:
            children[parent[v]].append(int(v))

    # bottom-up: each peer sends when all children reported or at its
    # deadline, whichever is first, and never before its own result
    send_t = np.zeros(n)
    merged_v = [None] * n
    merged_o = [None] * n
    late_urgent: list = []
    for v in order[::-1]:
        arrivals = [(send_t[c] + (lat_up[c] + list_bytes / bw_up[c]), c)
                    for c in children[v]]
        own_ready = t_ex_done[v]
        all_in = max([a for a, _ in arrivals], default=0.0)
        s = min(max(own_ready, all_in), max(deadline[v], own_ready))
        if death[v] < s:
            send_t[v] = np.inf            # left before sending
            continue
        send_t[v] = s
        mats = [scores[v]]
        owns = [np.full(p.k, v, dtype=np.int64)]
        for a, c in arrivals:
            if merged_v[c] is None:
                if dynamic:               # 4.2: grandchildren reroute
                    for cc in children[c]:
                        if merged_v[cc] is not None and send_t[cc] < np.inf:
                            mats.append(merged_v[cc])
                            owns.append(merged_o[cc])
                            m_bw += 1
                            b_bw += list_bytes
                continue
            if a <= s:
                mats.append(merged_v[c])
                owns.append(merged_o[c])
            elif dynamic:                 # 4.1: urgent list to the origin
                hops = depth[v]
                eta = a + hops * (p.latency_mean_s
                                  + list_bytes / p.bw_mean_Bps)
                late_urgent.append((eta, c))
                m_bw += int(hops)
                b_bw += int(hops) * list_bytes
        allm = np.concatenate(mats)
        allo = np.concatenate(owns)
        sel = np.argsort(allm)[::-1][:p.k]
        merged_v[v] = allm[sel]
        merged_o[v] = allo[sel]
        if v != origin:
            m_bw += 1
            b_bw += list_bytes

    t_merge_done = send_t[origin] + p.merge_s
    extra = [(merged_v[c], merged_o[c]) for eta, c in late_urgent
             if eta <= t_merge_done and merged_v[c] is not None]
    if extra:
        allm = np.concatenate([merged_v[origin]] + [e[0] for e in extra])
        allo = np.concatenate([merged_o[origin]] + [e[1] for e in extra])
        sel = np.argsort(allm)[::-1][:p.k]
        merged_v[origin] = allm[sel]
        merged_o[origin] = allo[sel]

    # retrieval: the origin fetches each winning owner's items directly;
    # an owner dead by then has lost them
    final_owners = np.unique(merged_o[origin])
    srv = death[final_owners] > t_merge_done
    lat_o, bw_o = draw_link(rng, p, len(final_owners))
    per_owner = np.array([(merged_o[origin] == o).sum()
                          for o in final_owners])
    fetch_bytes = per_owner * p.item_mean_B
    m_rt = 2 * int(srv.sum())
    b_rt = int(srv.sum() * p.request_B + fetch_bytes[srv].sum())
    t_fetch = (2 * lat_o + (p.request_B + fetch_bytes) / bw_o)[srv]
    response = float(t_merge_done + (t_fetch.max() if len(t_fetch) else 0.0))

    # accuracy: share of the reached set's true top-k that the origin got
    # (scores are distinct almost surely, so matching is by value)
    top_true = np.sort(scores[idx].reshape(-1))[::-1][:p.k]
    got = merged_v[origin]
    inter = np.intersect1d(top_true, got).size
    lost = np.isin(merged_o[origin], final_owners[~srv])
    inter = max(0, inter - int(np.isin(got[lost], top_true).sum()))
    return Answer(values=merged_v[origin], owners=merged_o[origin],
                  n_reached=len(idx), m_fw=m_fw, b_fw=m_fw * QUERY_BYTES,
                  m_bw=m_bw, b_bw=b_bw, m_rt=m_rt, b_rt=b_rt,
                  response_time_s=response, accuracy=inter / p.k)
