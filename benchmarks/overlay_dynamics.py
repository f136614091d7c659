"""Overlay dynamics — incremental NetworkPlan sync vs full recompile.

Exercises the live-overlay path (docs/OVERLAY.md) on a hierarchical
overlay (100k peers in the full run, 20k in ``--fast``):

* **single leave / join**: one `remove_peer` (with the "reconnect"
  repair) or one `add_peer`, then `plan.sync()` is timed against
  building a from-scratch `NetworkPlan` warmed on the same cached
  origins.  The ISSUE-9 acceptance criterion — sync >= 5x faster than
  the rebuild AND bit-exact with the rebuilt plan's query results on
  the scalar reference, the numpy sweep, and the jitted jax sweep, in
  both the shared and independent RNG modes — is asserted IN-BENCH
  (the run exits non-zero on violation) and re-enforced by the gate.
* **churn-rate sweep**: batches of join/leave events between syncs
  (`random_session` + "reconnect" repair), measuring how the
  incremental speedup decays as more cached BFS trees are invalidated
  per sync.  Floor: incremental must at least beat the rebuild (1x).
* **replication sweep**: top-k recall (accuracy) and the retrieval
  message/byte counts vs `SimParams.replication_factor` under heavy
  churn, with the numpy/jax/reference parity bit per row.

  PYTHONPATH=src python -m benchmarks.overlay_dynamics [--fast] [--out P]

writes ``BENCH_overlay_dynamics.json`` with suites
``overlay_dynamics`` (speedup floor 5x + parity), ``overlay_churn``
(floor 1x + parity) and ``overlay_replication`` (parity-only), all
gated by ``benchmarks/regression_gate.py`` against
``benchmarks/baselines/BENCH_overlay_dynamics.fast.json``.
"""
from __future__ import annotations

import json
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.engine import (NetworkPlan, Overlay, QuerySpec, SimEngine,
                          get_policy)
from repro.p2psim import SimParams, barabasi_albert, build_topology
from repro.p2psim.graph import bfs_tree_csr
from repro.p2psim.overlay import apply_events, random_session
from repro.p2psim.simulate import run_query_reference

_PARITY_FIELDS = ("m_fw", "m_bw", "m_rt", "b_bw", "b_rt",
                  "response_time_s", "accuracy")
_STRATEGY = "st1+2"


def _warm(plan: NetworkPlan, origins) -> None:
    """Compile statics + DepthSlices for ``origins`` (what a standing
    server holds for its hot query set)."""
    sts, _ = plan.origin_statics(np.asarray(origins, np.int64), 0,
                                 _STRATEGY)
    for st in sts:
        plan.depth_slices(st)


def _rebuild_s(ov: Overlay, origins) -> float:
    """Wall time for the from-scratch path: new plan + same warm set."""
    t0 = time.perf_counter()
    fresh = NetworkPlan(ov.top)
    _warm(fresh, origins)
    return time.perf_counter() - t0, fresh


def _parity(synced: NetworkPlan, fresh: NetworkPlan, top, origins,
            params, *, jax_too: bool) -> bool:
    """Synced-plan results == rebuilt-plan results == the scalar
    reference, numpy (+ optionally jax), shared + independent modes."""
    pol = get_policy("fd-dynamic").variant(lifetime_mean_s=30.0)
    engines = [SimEngine(fresh, params)]
    if jax_too:
        engines += [SimEngine(synced, params, backend="jax"),
                    SimEngine(fresh, params, backend="jax")]
    base_eng = SimEngine(synced, params)
    for rng in ("shared", "independent"):
        spec = QuerySpec(origins=tuple(origins), n_trials=1, rng=rng)
        base = base_eng.run(spec, pol).metrics
        for eng in engines:
            got = eng.run(spec, pol).metrics
            if not all(np.array_equal(getattr(base, f), getattr(got, f))
                       for f in _PARITY_FIELDS):
                return False
    ref, _ = run_query_reference(top, int(origins[0]), params,
                                 dynamic=True, lifetime_mean_s=30.0)
    one = base_eng.run(QuerySpec(origins=(int(origins[0]),)), pol)
    return one.query_metrics(0, 0) == ref


def _deep_leaf(plan: NetworkPlan, origin: int) -> int:
    """A degree-1 peer as deep as possible below ``origin`` — the
    common 'edge-of-the-network peer departs' case."""
    _, depth, _ = bfs_tree_csr(plan.indptr, plan.indices, origin,
                               plan.top.n)
    cand = np.where(plan.degrees == 1, depth, -1)
    if cand.max() < 1:                      # no leaves: deepest low-degree
        cand = np.where(plan.degrees <= 2, depth, -1)
    return int(cand.argmax())


def incremental_sync_rows(fast: bool):
    """Single leave / join on the big hierarchical overlay."""
    n_peers = 20_000 if fast else 100_000
    n_origins = 8 if fast else 16
    params = SimParams(seed=0)
    rows = []
    for event in ("leave", "join"):
        top = build_topology("hierarchical", n_peers, seed=7)
        ov = Overlay(top)
        plan = NetworkPlan(ov)
        rng = np.random.default_rng(11)
        origins = sorted(int(o) for o in
                         rng.choice(n_peers, n_origins, replace=False))
        _warm(plan, origins)
        if event == "leave":
            ov.remove_peer(_deep_leaf(plan, origins[0]),
                           repair="reconnect")
        else:
            nbs = (origins[0], int(ov.top.neighbors[origins[0]][0]))
            ov.add_peer(neighbors=nbs)
        t0 = time.perf_counter()
        assert plan.sync() is True
        sync_s = time.perf_counter() - t0
        rebuild_s, fresh = _rebuild_s(ov, origins)
        speedup = rebuild_s / max(sync_s, 1e-9)
        parity = _parity(plan, fresh, ov.top, origins[:2], params,
                         jax_too=True)
        row = {"suite": "overlay_dynamics", "event": event,
               "n_peers": n_peers, "n_cached_origins": n_origins,
               "sync_s": round(sync_s, 4),
               "rebuild_s": round(rebuild_s, 4),
               "speedup": round(speedup, 2), "parity": parity}
        print(f"[overlay_dynamics] {event:<5s} n={n_peers}  "
              f"sync {sync_s*1e3:8.1f} ms  rebuild {rebuild_s*1e3:8.1f} "
              f"ms  speedup {speedup:6.2f}x  parity={parity}")
        rows.append(row)
        # ISSUE-9 acceptance: >= 5x and bit-exact, asserted in-bench
        assert speedup >= 5.0, (
            f"incremental sync after a single {event} is only "
            f"{speedup:.2f}x faster than a full rebuild (need >= 5x)")
        assert parity, f"synced plan diverged from rebuild after {event}"
    return rows


def churn_sweep_rows(fast: bool):
    """Speedup decay as more events land between syncs."""
    n_peers = 20_000 if fast else 100_000
    n_origins = 8 if fast else 16
    params = SimParams(seed=0)
    top = build_topology("hierarchical", n_peers, seed=7)
    ov = Overlay(top)
    plan = NetworkPlan(ov)
    rng = np.random.default_rng(13)
    origins = sorted(int(o) for o in
                     rng.choice(n_peers, n_origins, replace=False))
    _warm(plan, origins)
    rows = []
    for i, events_per_sync in enumerate((2, 8, 32)):
        events = random_session(ov, events_per_sync, seed=100 + i,
                                join_prob=0.5)
        apply_events(ov, events, repair="reconnect")
        t0 = time.perf_counter()
        assert plan.sync() is True
        sync_s = time.perf_counter() - t0
        rebuild_s, fresh = _rebuild_s(ov, origins)
        speedup = rebuild_s / max(sync_s, 1e-9)
        parity = _parity(plan, fresh, ov.top, origins[:2], params,
                         jax_too=False)
        row = {"suite": "overlay_churn",
               "events_per_sync": events_per_sync, "n_peers": n_peers,
               "n_cached_origins": n_origins,
               "sync_s": round(sync_s, 4),
               "rebuild_s": round(rebuild_s, 4),
               "speedup": round(speedup, 2), "parity": parity}
        print(f"[overlay_churn] events={events_per_sync:<3d} "
              f"sync {sync_s*1e3:8.1f} ms  rebuild {rebuild_s*1e3:8.1f} "
              f"ms  speedup {speedup:6.2f}x  parity={parity}")
        rows.append(row)
        assert parity, "synced plan diverged from rebuild under churn"
    return rows


def replication_rows(fast: bool):
    """Top-k recall / retrieval traffic vs replication factor under
    heavy churn (mean peer lifetime ~ the query horizon)."""
    n_peers = 2_000 if fast else 10_000
    top = barabasi_albert(n_peers, m=2, seed=5)
    pol = get_policy("fd-dynamic").variant(lifetime_mean_s=8.0)
    spec = QuerySpec(origins=(0, 7, 101, 999), n_trials=4,
                     rng="independent")
    rows = []
    for r, placement in ((0, "random"), (2, "random"), (4, "random"),
                         (2, "neighbor")):
        params = SimParams(seed=3, replication_factor=r,
                           replication_placement=placement)
        m_np = SimEngine(top, params).run(spec, pol).metrics
        m_jx = SimEngine(top, params, backend="jax").run(spec,
                                                         pol).metrics
        parity = all(np.array_equal(getattr(m_np, f), getattr(m_jx, f))
                     for f in _PARITY_FIELDS)
        ref, _ = run_query_reference(top, 0, params, dynamic=True,
                                     lifetime_mean_s=8.0)
        one = SimEngine(top, params).run(
            QuerySpec(origins=(0,)), pol)
        parity = parity and one.query_metrics(0, 0) == ref
        row = {"suite": "overlay_replication", "replication_factor": r,
               "placement": placement, "n_peers": n_peers,
               "recall": round(float(m_np.accuracy.mean()), 4),
               "m_rt": round(float(m_np.m_rt.mean()), 2),
               "b_rt": round(float(m_np.b_rt.mean()), 1),
               "m_bw": round(float(m_np.m_bw.mean()), 2),
               "parity": parity}
        print(f"[overlay_replication] r={r} {placement:<9s} "
              f"recall {row['recall']:.3f}  m_rt {row['m_rt']:8.1f}  "
              f"parity={parity}")
        rows.append(row)
        assert parity, f"replication r={r}/{placement} broke parity"
    base = next(x for x in rows if x["replication_factor"] == 0)
    best = max(x["recall"] for x in rows if x["replication_factor"] > 0)
    assert best >= base["recall"], \
        "replication failed to recover recall under churn"
    return rows


def collect(fast: bool = False) -> dict:
    rows = (incremental_sync_rows(fast) + churn_sweep_rows(fast)
            + replication_rows(fast))
    return {
        "meta": {"created_unix": time.time(), "fast": fast,
                 "numpy": np.__version__},
        "results": rows,
    }


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke sizes (gate against the committed "
                         "fast baseline)")
    ap.add_argument("--out", default="BENCH_overlay_dynamics.json")
    args = ap.parse_args()
    enable_compile_cache()
    data = collect(fast=args.fast)
    with open(args.out, "w") as f:
        json.dump(data, f, indent=1)
    print(f"wrote {args.out} ({len(data['results'])} rows)")


if __name__ == "__main__":
    main()
