"""Serving load generator — QueryServer under ramping concurrent load.

Drives a warm :class:`repro.engine.QueryServer` with mixed
policy/topology request streams at ramping concurrency (closed-loop
client threads, topping out at >= 64 in-flight requests even in
``--fast``), and measures the serving numbers the paper's deployment
story rests on: sustained throughput, p50/p95/p99 latency, and how much
dynamic batching actually coalesced.  Every stage also replays its
request list through one-at-a-time ``Engine.run()`` calls and asserts
the served results are entry-wise BIT-EXACT — the batcher must change
scheduling, never bits — across every policy and RNG mode in the mix
(shared batch-of-1, independent streams, explicit seed grids, and
non-coalescable shared multi-entry specs).

  PYTHONPATH=src python -m benchmarks.loadgen [--fast] [--out PATH]

writes ``BENCH_serving.json``:

  {
    "meta":    {"created_unix": float, "fast": bool, "numpy": str},
    "results": [
      {"suite": "serving", "backend": "numpy"|"jax", "concurrency": int,
       "n_requests": int, "n_engines": int, "n_policies": int,
       "wall_s": float, "throughput_qps": float, "p50_ms": float,
       "p95_ms": float, "p99_ms": float, "mean_batch": float,
       "max_batch": int, "batched_frac": float, "shed": int,
       "timed_out": int, "parity": bool, "batched": bool}
    ]
  }

``parity`` (bit-exact vs sequential ``run()``) and ``batched`` (fusion
> 1 actually occurred) are required bits; ``throughput_qps`` carries an
absolute floor — all enforced by ``benchmarks/regression_gate.py``
against ``benchmarks/baselines/BENCH_serving.fast.json`` (see
docs/SERVING.md for reading these rows).
"""
from __future__ import annotations

import json
import threading
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.engine import (QueryServer, QuerySpec, ServerConfig, SimEngine,
                          ServerError)
from repro.p2psim import SimParams, build_topology

POLICIES = ("fd-dynamic", "cn", "cn-star", "fd-st1+2")
TOPOLOGIES = ("ba", "small-world")
_PARITY_FIELDS = ("n_reached", "n_edges_pq", "m_fw", "m_bw", "m_rt",
                  "b_fw", "b_bw", "b_rt", "response_time_s", "accuracy")


def _mixed_requests(n: int, n_peers: int, engine_names, policies, seed=0):
    """A request stream covering every RNG mode and both batcher paths.

    Cycles through shared batch-of-1, independent multi-entry, explicit
    seed-grid (all coalescable) and shared multi-entry (runs solo)
    specs, with policies and engines assigned round-robin.
    """
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        o = int(rng.integers(n_peers))
        o2 = int(rng.integers(n_peers))
        s = int(rng.integers(1 << 30))
        kind = i % 4
        if kind == 0:          # shared stream, batch of 1 (coalesces)
            spec = QuerySpec(origins=(o,), seed=s)
        elif kind == 1:        # independent streams (coalesces)
            spec = QuerySpec(origins=(o, o2), n_trials=2,
                             rng="independent", seed=s)
        elif kind == 2:        # explicit seed grid (coalesces)
            spec = QuerySpec(origins=(o,), n_trials=2,
                             seeds=[[s, s + 1]])
        else:                  # shared multi-entry (must run solo)
            spec = QuerySpec(origins=(o, o2), n_trials=2, seed=s)
        reqs.append((spec, policies[i % len(policies)],
                     engine_names[i % len(engine_names)]))
    return reqs


def _metrics_equal(a, b) -> bool:
    return all(np.array_equal(getattr(a, f), getattr(b, f))
               for f in _PARITY_FIELDS)


def _closed_loop(server, reqs, concurrency: int):
    """Run ``reqs`` through ``server`` with ``concurrency`` client
    threads; returns (results, per-request latencies, wall seconds,
    server errors)."""
    results = [None] * len(reqs)
    lat = [0.0] * len(reqs)
    errors = []
    cursor = {"i": 0}
    lock = threading.Lock()

    def client():
        while True:
            with lock:
                i = cursor["i"]
                if i >= len(reqs):
                    return
                cursor["i"] = i + 1
            spec, pol, name = reqs[i]
            t0 = time.perf_counter()
            try:
                results[i] = server.query(spec, pol, engine=name)
            except ServerError as e:
                errors.append((i, e))
            lat[i] = time.perf_counter() - t0

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client)
               for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results, lat, time.perf_counter() - t0, errors


def _stage_row(engines, reqs, concurrency: int, backend: str,
               n_policies: int, max_batch: int = 64) -> dict:
    """One ramp stage: serve ``reqs``, then replay sequentially for the
    bit-exactness bit."""
    server = QueryServer(engines, ServerConfig(
        max_queue=max(256, 2 * concurrency), max_batch=max_batch,
        batch_window_s=0.002))
    with server:
        results, lat, wall, errors = _closed_loop(server, reqs,
                                                  concurrency)
        m = server.metrics()
    if errors:                        # nothing should shed at this bound
        raise AssertionError(f"{len(errors)} requests failed: "
                             f"{errors[0][1]!r}")
    parity = True
    for (spec, pol, name), res in zip(reqs, results):
        ref = engines[name].run(spec, pol)
        if not _metrics_equal(res.metrics, ref.metrics):
            parity = False
            break
    hist = m.batch_hist
    n_hist = sum(hist.values())
    batched_frac = (sum(c for s, c in hist.items() if s > 1)
                    / max(n_hist, 1))
    lat_ms = np.asarray(lat) * 1e3
    return {
        "suite": "serving", "backend": backend,
        "concurrency": concurrency, "n_requests": len(reqs),
        "n_engines": len(engines), "n_policies": n_policies,
        "wall_s": round(wall, 4),
        "throughput_qps": round(len(reqs) / max(wall, 1e-9), 2),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p95_ms": round(float(np.percentile(lat_ms, 95)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "mean_batch": round(m.mean_batch, 3),
        "max_batch": int(m.max_batch),
        "batched_frac": round(batched_frac, 3),
        "shed": m.shed, "timed_out": m.timed_out,
        "retraced": sum(1 for r in results
                        if r is not None and r.compile_s > 0),
        "parity": parity, "batched": m.max_batch > 1,
    }


def serving_sweep(fast: bool = False):
    """The ramp: mixed-stream stages at growing concurrency (numpy),
    plus a shape-stable jax-backend batching-parity stage."""
    results = []
    n_peers = 400 if fast else 1000
    policies = POLICIES[:3] if fast else POLICIES
    engines = {name: SimEngine(build_topology(name, n_peers, seed=7),
                               SimParams(seed=0))
               for name in TOPOLOGIES}
    names = sorted(engines)
    for name in names:
        for pol in policies:          # warm plans before taking load
            engines[name].run(QuerySpec(origins=(0,)), pol)
    stages = ((8, 64), (32, 128), (64, 192)) if fast else \
        ((8, 128), (16, 256), (32, 384), (64, 512), (128, 768))
    for concurrency, n_requests in stages:
        reqs = _mixed_requests(n_requests, n_peers, names, policies,
                               seed=concurrency)
        row = _stage_row(engines, reqs, concurrency, "numpy",
                         len(policies))
        print(f"[serving] numpy c={concurrency:<4d} "
              f"{row['throughput_qps']:>8.1f} qps  p50/p95/p99 "
              f"{row['p50_ms']:.1f}/{row['p95_ms']:.1f}/"
              f"{row['p99_ms']:.1f} ms  mean batch {row['mean_batch']:.2f}"
              f"  parity={row['parity']}")
        results.append(row)
        assert row["parity"], "served results diverged from run()"
        assert row["batched"], "dynamic batching never fused requests"
    # jax stage: jitted sweeps are trace-cached per (origin statics,
    # entry-bucket) — entry batches pad to power-of-two buckets, so
    # pre-warming each served origin at batch sizes (1, 2, 4) via
    # QueryServer.warm covers EVERY fused dispatch shape max_batch=4
    # can produce.  Live dispatches must then retrace nothing
    # (asserted: retraced == 0, i.e. compile_s == 0 on every request).
    jax_c, jax_n = (8, 32) if fast else (16, 96)
    jax_engines = {"ba": SimEngine(build_topology("ba", n_peers, seed=7),
                                   SimParams(seed=0), backend="jax")}
    rng = np.random.default_rng(1)
    pool = tuple(int(x) for x in rng.choice(n_peers, 4, replace=False))
    reqs = [(QuerySpec(origins=(pool[i % len(pool)],),
                       seed=int(rng.integers(1 << 30))),
             "fd-dynamic", "ba") for i in range(jax_n)]
    warm_srv = QueryServer(jax_engines)
    for o in pool:                               # trace every bucket
        warm_srv.warm(QuerySpec(origins=(o,), seed=1), "fd-dynamic",
                      batch_sizes=(1, 2, 4))
    row = _stage_row(jax_engines, reqs, jax_c, "jax", 1, max_batch=4)
    print(f"[serving] jax   c={jax_c:<4d} {row['throughput_qps']:>8.1f} "
          f"qps  mean batch {row['mean_batch']:.2f}  "
          f"parity={row['parity']} batched={row['batched']} "
          f"retraced={row['retraced']}")
    assert row["parity"], "jax served results diverged from run()"
    assert row["retraced"] == 0, \
        "warmed buckets still retraced at dispatch"
    results.append(row)
    return results


def collect(fast: bool = False) -> dict:
    rows = serving_sweep(fast)
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
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()
    enable_compile_cache()
    data = collect(fast=args.fast)
    with open(args.out, "w") as f:
        json.dump(data, f, indent=2)
    print(f"wrote {args.out} ({len(data['results'])} rows)")


if __name__ == "__main__":
    main()
