"""Batched multi-query benchmark suite (engine entrypoint).

Sweeps ``SimEngine`` over (n_peers, k, churn, policy) and the TPU-side
collectives over (schedule, k), and measures two headline numbers:

  * ``speedup`` — one batched engine call vs a Python loop of scalar
    ``run_query_reference`` calls (the PR-1 acceptance measurement);
  * ``plan_cache`` — a warm engine (compiled ``NetworkPlan`` reused
    across ``run`` calls) vs a cold engine built per call (the ISSUE-2
    acceptance measurement; CI asserts warm beats cold).

  PYTHONPATH=src python -m benchmarks.multi_query [--fast] [--out PATH]

writes ``BENCH_multi_query.json``:

  {
    "meta":    {"created_unix": float, "fast": bool, "jax": str,
                "numpy": str},
    "results": [
      {"suite": "sim",   "n_peers": int, "k": int, "policy": str,
       "lifetime_s": float|null, "n_queries": int, "n_trials": int,
       "wall_s": float, "queries_per_s": float,
       "mean_total_bytes": float, "mean_total_messages": float,
       "mean_response_s": float, "mean_accuracy": float},
      {"suite": "speedup", "n_peers": int, "n_queries": int,
       "n_trials": int, "batch_s": float, "loop_s": float,
       "speedup": float},
      {"suite": "plan_cache", "n_peers": int, "n_queries": int,
       "n_trials": int, "n_policies": int, "warm_s": float,
       "cold_s": float, "speedup": float},
      {"suite": "jax_backend", "n_peers": int, "k": int,
       "n_queries": int, "n_trials": int, "jax_s": float,
       "numpy_s": float, "reference_s": float, "speedup": float,
       "vs_batch_numpy": float, "parity": bool},
      {"suite": "jax_churn", "n_peers": int, "k": int,
       "lifetime_s": float, "n_queries": int, "n_trials": int,
       "jax_s": float, "numpy_s": float, "reference_s": float,
       "speedup": float, "vs_batch_numpy": float, "parity": bool},
      {"suite": "precision", "n_peers": int, "k": int, "precision": str,
       "n_queries": int, "n_trials": int, "platform": str,
       "jax64_s": float, "jax_s": float, "speedup_vs_f64": float,
       "recall": float, "max_rtol": float, "separated": bool,
       "tol_ok": bool, "parity": bool},
      {"suite": "precision_scale", "n_peers": int, "k": int,
       "index_dtype": str, "precision": str, "build_s": float,
       "run_s": float, "recall": float, "max_rtol": float,
       "tol_ok": bool, "parity": bool},
      {"suite": "topology_sweep", "topology": str, "latency_model": str,
       "n_peers": int, "k": int, "n_queries": int, "n_trials": int,
       "numpy_s": float, "jax_s": float, "vs_numpy": float,
       "mean_m_bw": float, "mean_response_s": float,
       "mean_total_bytes": float, "parity": bool},
      {"suite": "tpu", "schedule": str, "k": int, "n_dev": int,
       "n_local": int, "model_bytes": int, "measured_bytes": int,
       "wall_us_per_call": float}
    ]
  }
"""
from __future__ import annotations

import dataclasses
import json
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.engine import NetworkPlan, QuerySpec, SimEngine, get_policy
from repro.p2psim import (SimParams, available_topologies,
                          barabasi_albert, build_topology,
                          run_query_reference)

SIM_POLICIES = ("fd-dynamic", "cn", "cn-star")
_PARITY_FIELDS = ("n_reached", "n_edges_pq", "m_fw", "m_bw", "m_rt",
                  "b_fw", "b_bw", "b_rt", "response_time_s", "accuracy")


def sim_sweep(fast: bool = False):
    results = []
    sizes = (128, 256) if fast else (128, 256, 512)
    ks = (20,) if fast else (10, 20)
    lifetimes = (None,) if fast else (None, 60.0)
    nq, nt = (16, 2) if fast else (32, 4)
    for n_peers in sizes:
        top = barabasi_albert(n_peers, m=2, seed=7)
        origins = tuple(int(o) for o in np.random.default_rng(0)
                        .integers(0, n_peers, nq))
        engine = SimEngine(top)       # NetworkPlan shared by the sweep
        for k in ks:
            spec = QuerySpec(origins=origins, n_trials=nt, k=k, seed=0)
            for lt in lifetimes:
                for name in SIM_POLICIES:
                    pol = get_policy(name)
                    if lt is not None:
                        pol = pol.variant(lifetime_mean_s=lt)
                    engine.run(spec, pol)   # warm the plan so every row
                    t0 = time.perf_counter()  # times execution, not build
                    bm = engine.run(spec, pol).metrics
                    wall = time.perf_counter() - t0
                    results.append({
                        "suite": "sim", "n_peers": n_peers, "k": k,
                        "policy": name, "lifetime_s": lt,
                        "n_queries": nq, "n_trials": nt, "wall_s": wall,
                        "queries_per_s": nq * nt / wall,
                        "mean_total_bytes": float(bm.total_bytes.mean()),
                        "mean_total_messages": float(
                            bm.total_messages.mean()),
                        "mean_response_s": float(
                            bm.response_time_s.mean()),
                        "mean_accuracy": float(bm.accuracy.mean()),
                    })
    return results


def speedup_bench(fast: bool = False):
    """Batched engine call vs scalar-reference loop, best-of-N."""
    n_peers, nq, nt = 256, 64, 4
    top = barabasi_albert(n_peers, m=2, seed=7)
    p = SimParams(seed=5)
    origins = np.random.default_rng(0).integers(0, n_peers, nq)
    engine = SimEngine(top, p)
    spec = QuerySpec(origins=tuple(int(o) for o in origins), n_trials=nt)
    engine.run(spec)                                  # warm numpy caches
    reps_b, reps_l = (3, 1) if fast else (5, 2)
    batch_s = min(_timed(lambda: SimEngine(top, p).run(spec))
                  for _ in range(reps_b))             # cold, like the loop
    def loop():
        for q in range(nq):
            for t in range(nt):
                run_query_reference(
                    top, int(origins[q]),
                    dataclasses.replace(p, seed=p.seed + q * nt + t))
    loop_s = min(_timed(loop) for _ in range(reps_l))
    return [{"suite": "speedup", "n_peers": n_peers, "n_queries": nq,
             "n_trials": nt, "batch_s": batch_s, "loop_s": loop_s,
             "speedup": loop_s / batch_s}]


def plan_cache_bench(fast: bool = False):
    """Warm NetworkPlan reuse vs cold per-call preprocessing.

    The warm engine runs the same workload (three policies over the same
    origin set) on one prepared engine; the cold side builds a fresh
    ``SimEngine`` — CSR, directed edges, BFS trees, forward masks — for
    every call, which is exactly what the legacy ``run_queries`` shim
    does.  Best-of-N both sides.
    """
    n_peers, nq, nt = 256, 64, 1
    top = barabasi_albert(n_peers, m=2, seed=7)
    p = SimParams(seed=3)
    spec = QuerySpec(origins=tuple(int(o) for o in np.random.default_rng(1)
                                   .integers(0, n_peers, nq)), n_trials=nt)
    engine = SimEngine(top, p)
    def warm():
        for name in SIM_POLICIES:
            engine.run(spec, name)
    def cold():
        for name in SIM_POLICIES:
            SimEngine(top, p).run(spec, name)
    warm()                                            # populate the plan
    reps = 5                    # best-of-5 even in --fast: the CI gate
    warm_s = min(_timed(warm) for _ in range(reps))   # asserts warm < cold
    cold_s = min(_timed(cold) for _ in range(reps))
    return [{"suite": "plan_cache", "n_peers": n_peers, "n_queries": nq,
             "n_trials": nt, "n_policies": len(SIM_POLICIES),
             "warm_s": warm_s, "cold_s": cold_s,
             "speedup": cold_s / warm_s}]


def jax_backend_bench(fast: bool = False):
    """SimEngine(backend="jax") on a Gnutella-shaped BA overlay (§5.1).

    The acceptance measurement of the jitted backend: the same
    independent-streams workload is run through

      * the jitted JAX engine (``speedup`` numerator's subject),
      * the scalar ``run_query_reference`` loop — the paper-fidelity
        numpy simulator every engine is bit-exact against
        (``reference_s``; the suite's ``speedup`` convention, like the
        PR-1 batched-vs-scalar acceptance row), and
      * the vectorized numpy batch backend (``vs_batch_numpy``) — on a
        2-core CPU the f64 merge sweeps of both backends are memory
        bound and land near parity; the jitted path pulls ahead on
        accelerators where the Pallas merge kernel lowers natively.

    Entry-wise bit-parity between the jax engine and the scalar
    reference is ASSERTED here at full scale (``parity``), so the
    speedup rows can never drift away from the exactness contract.
    """
    n_peers = 20_000 if fast else 100_000
    nq, nt = 2, 2
    top = barabasi_albert(n_peers, m=2, seed=7)
    p = SimParams(seed=5)
    spec = QuerySpec(origins=(0, 1), n_trials=nt, seed=5,
                     rng="independent")
    eng_np = SimEngine(top, p)
    eng_jx = SimEngine(top, p, backend="jax")
    eng_np.run(spec)                      # warm plans + jit caches
    eng_jx.run(spec)
    reps = 2 if fast else 3
    numpy_s = min(_timed(lambda: eng_np.run(spec)) for _ in range(reps))
    jax_s = min(_timed(lambda: eng_jx.run(spec)) for _ in range(reps))
    res = eng_jx.run(spec)
    t0 = time.perf_counter()
    parity = True
    for q in range(nq):
        for t in range(nt):
            met, _ = run_query_reference(
                top, q, dataclasses.replace(p, seed=p.seed + q * nt + t))
            parity = parity and res.query_metrics(q, t) == met
    reference_s = time.perf_counter() - t0
    assert parity, "jax backend diverged from run_query_reference"
    return [{"suite": "jax_backend", "n_peers": n_peers, "k": p.k,
             "n_queries": nq, "n_trials": nt, "jax_s": jax_s,
             "numpy_s": numpy_s, "reference_s": reference_s,
             "speedup": reference_s / jax_s,
             "vs_batch_numpy": numpy_s / jax_s, "parity": parity}]


def jax_churn_bench(fast: bool = False):
    """SimEngine(backend="jax") under churn (§4/§5.4) at overlay scale.

    The acceptance measurement of the churn-aware jitted sweep: the
    scenarios the paper cares most about — peers leaving mid-query,
    urgent forwarding, dead-parent rerouting — across several lifetime
    regimes (heavy churn where a meaningful fraction of peers dies
    before sending, and light churn where deaths are rare but the
    masked/reroute-augmented sweep still runs).  Per regime the same
    independent-streams workload runs through the jitted engine, the
    vectorized numpy backend, and a scalar ``run_query_reference``
    loop; entry-wise bit-parity with the reference is ASSERTED at full
    scale, as is the absence of any numpy fallback
    (``backend_used == "sim-jax"``).
    """
    n_peers = 20_000 if fast else 100_000
    nq, nt = 2, 2
    lifetimes = (60.0, 600.0)
    top = barabasi_albert(n_peers, m=2, seed=7)
    p = SimParams(seed=5)
    spec = QuerySpec(origins=(0, 1), n_trials=nt, seed=5,
                     rng="independent")
    eng_np = SimEngine(top, p)
    eng_jx = SimEngine(top, p, backend="jax")
    reps = 2 if fast else 3
    results = []
    for lt in lifetimes:
        pol = get_policy("fd-dynamic").variant(lifetime_mean_s=lt)
        eng_np.run(spec, pol)             # warm plans + jit caches
        eng_jx.run(spec, pol)
        numpy_s = min(_timed(lambda: eng_np.run(spec, pol))
                      for _ in range(reps))
        jax_s = min(_timed(lambda: eng_jx.run(spec, pol))
                    for _ in range(reps))
        res = eng_jx.run(spec, pol)
        assert res.backend_used == "sim-jax", "churn fell back to numpy"
        t0 = time.perf_counter()
        parity = True
        for q in range(nq):
            for t in range(nt):
                met, _ = run_query_reference(
                    top, q,
                    dataclasses.replace(p, seed=p.seed + q * nt + t),
                    lifetime_mean_s=lt)
                parity = parity and res.query_metrics(q, t) == met
        reference_s = time.perf_counter() - t0
        assert parity, ("jax churn backend diverged from "
                        f"run_query_reference (lifetime {lt})")
        results.append({
            "suite": "jax_churn", "n_peers": n_peers, "k": p.k,
            "lifetime_s": lt, "n_queries": nq, "n_trials": nt,
            "jax_s": jax_s, "numpy_s": numpy_s,
            "reference_s": reference_s,
            "speedup": reference_s / jax_s,
            "vs_batch_numpy": numpy_s / jax_s, "parity": parity})
    return results


def precision_bench(fast: bool = False):
    """Reduced-precision jax sweeps vs the f64 jax sweep (ISSUE 10).

    Per precision mode the same independent-streams workload runs
    through the reduced-precision engine twice: untimed WITH validation
    (recording the tolerance contract — top-k owner recall + positional
    score rtol vs the engine's own f64 rerun) and timed WITHOUT
    (``validate_precision=False``, so the timed path is the reduced
    sweep alone).  The tolerance ``ok`` bit is ASSERTED for every row —
    and recall == 1.0 outright whenever the f64 scores are separated at
    the cast's resolution (bf16 spacing near 1.0 is ~0.004, so U(0,1)
    top scores legitimately collapse into ties there; the contract
    exempts recall exactly then, see docs/BENCHMARKS.md PRECISION).

    ``speedup_vs_f64`` is the acceptance ratio on accelerator
    platforms (asserted >= 1.5 for f32 in the full sweep there); on CPU
    the f64 sweep is already memory-bound and vectorized, the ratio
    lands near 1x and only the tolerance bits gate (same convention as
    the serving suite's compile-dominated jax rows).
    """
    import jax
    n_peers = 20_000 if fast else 100_000
    nq, nt = 2, 2
    platform = jax.default_backend()
    top = barabasi_albert(n_peers, m=2, seed=7)
    p = SimParams(seed=5)
    spec = QuerySpec(origins=(0, 1), n_trials=nt, seed=5,
                     rng="independent")
    plan = NetworkPlan(top)              # shared: one BFS per origin
    eng64 = SimEngine(plan, p, backend="jax")
    eng64.run(spec)                      # warm plan + jit caches
    reps = 2 if fast else 3
    f64_s = min(_timed(lambda: eng64.run(spec)) for _ in range(reps))
    rows = []
    for prec in ("f32", "bf16"):
        eng = SimEngine(plan, p, backend="jax", precision=prec,
                        validate_precision=False)
        eng.run(spec)
        lo_s = min(_timed(lambda: eng.run(spec)) for _ in range(reps))
        veng = SimEngine(plan, p, backend="jax", precision=prec)
        tol = veng.run(spec).extras["tolerance"]
        assert tol["ok"], f"{prec} tolerance contract violated: {tol}"
        if tol["separated"]:
            assert tol["recall"] == 1.0, (prec, tol)
        row = {"suite": "precision", "n_peers": n_peers, "k": p.k,
               "precision": prec, "n_queries": nq, "n_trials": nt,
               "platform": platform, "jax64_s": f64_s, "jax_s": lo_s,
               "speedup_vs_f64": f64_s / lo_s, "recall": tol["recall"],
               "max_rtol": tol["max_rtol"],
               "separated": tol["separated"], "tol_ok": tol["ok"],
               "parity": tol["ok"]}
        if prec == "f32" and platform != "cpu" and not fast:
            assert row["speedup_vs_f64"] >= 1.5, (
                "accelerator acceptance: f32 sweep must be >= 1.5x "
                f"over f64, got {row['speedup_vs_f64']:.2f}x")
        rows.append(row)
    return rows


def precision_scale_bench(fast: bool = False):
    """1M-peer plan under int32 indices + f32 sweep (ISSUE 10 memory
    acceptance: the plan must build AND answer a query on one host).

    A star overlay (1M spokes sharing one literal neighbor array keeps
    the host-side build cheap) exercises the widest single level the
    sweep can see — (1, 1M) level arrays — with every index array
    int32 and every float array f32; the run is validated against the
    engine's own f64 rerun, so the tolerance bit gates here too.  Runs
    in BOTH the fast and full legs.
    """
    from repro.p2psim.graph import Topology
    n = 1_000_000
    hub = np.arange(1, n, dtype=np.int32)
    spoke = np.array([0], dtype=np.int32)   # shared by all 1M spokes
    top = Topology(n=n, neighbors=[hub] + [spoke] * (n - 1), kind="star")
    t0 = time.perf_counter()
    plan = NetworkPlan(top, index_dtype="int32")
    build_s = time.perf_counter() - t0
    assert plan.index_dtype == np.int32
    assert plan.edge_keys.dtype == np.int64     # n^2 > 2^31: stays wide
    eng = SimEngine(plan, SimParams(seed=3), backend="jax",
                    precision="f32")
    t0 = time.perf_counter()
    res = eng.run(QuerySpec(origins=(0,), seed=3))
    run_s = time.perf_counter() - t0
    tol = res.extras["tolerance"]
    assert tol["ok"], f"1M-peer f32 tolerance contract violated: {tol}"
    return [{"suite": "precision_scale", "n_peers": n, "k": 20,
             "index_dtype": "int32", "precision": "f32",
             "build_s": build_s, "run_s": run_s,
             "recall": tol["recall"], "max_rtol": tol["max_rtol"],
             "tol_ok": tol["ok"], "parity": tol["ok"]}]


def topology_sweep(fast: bool = False):
    """Every registered topology family through BOTH sim backends.

    The ISSUE-5 acceptance measurement: per family the same
    independent-streams workload runs through the numpy and the jitted
    JAX engine (one shared ``NetworkPlan``), under the per-edge BRITE
    latency model wherever the family carries coordinates (``"iid"``
    for flat BA, which has no embedding) — and entry-wise metric
    equality between the two backends is ASSERTED (``parity``), at
    100k-peer scale for the hierarchical family in the full sweep.  The
    recorded ``mean_m_bw`` / ``mean_response_s`` rows are the
    cross-family comparison the paper's §5 response-time results can be
    read against: topology shape (power-law vs. random vs. hierarchical
    vs. degree-homogeneous) and the distance-derived latencies both
    move the traffic and latency outcomes.

    The hierarchical family runs at ``n_hier`` (100k full, 20k fast);
    the flat families at ``n_flat``; Waxman at its O(n^2)-build scale.
    """
    n_flat = 2_000 if fast else 20_000
    n_hier = 20_000 if fast else 100_000
    nq, nt = 2, 2
    reps = 2 if fast else 3
    results = []
    for name in available_topologies():
        n_peers = {"hierarchical": n_hier,
                   "waxman": min(n_flat, 2_000)}.get(name, n_flat)
        top = build_topology(name, n_peers, seed=7)
        lm = "edge" if top.coords is not None else "iid"
        p = SimParams(seed=5, latency_model=lm)
        spec = QuerySpec(origins=(0, 1), n_trials=nt, seed=5,
                         rng="independent")
        plan = NetworkPlan(top)               # shared: one BFS per origin
        eng_np = SimEngine(plan, p)
        eng_jx = SimEngine(plan, p, backend="jax")
        eng_np.run(spec)                      # warm plan + jit caches
        eng_jx.run(spec)
        numpy_s = min(_timed(lambda: eng_np.run(spec))
                      for _ in range(reps))
        jax_s = min(_timed(lambda: eng_jx.run(spec)) for _ in range(reps))
        rn = eng_np.run(spec)
        rj = eng_jx.run(spec)
        assert rj.backend_used == "sim-jax"
        parity = all(
            np.array_equal(getattr(rn.metrics, f), getattr(rj.metrics, f))
            for f in _PARITY_FIELDS)
        assert parity, (f"jax backend diverged from numpy on topology "
                        f"{name!r} ({lm} latency, n={n_peers})")
        results.append({
            "suite": "topology_sweep", "topology": name,
            "latency_model": lm, "n_peers": n_peers, "k": p.k,
            "n_queries": nq, "n_trials": nt,
            "numpy_s": numpy_s, "jax_s": jax_s,
            "vs_numpy": numpy_s / jax_s,
            "mean_m_bw": float(rn.metrics.m_bw.mean()),
            "mean_response_s": float(rn.metrics.response_time_s.mean()),
            "mean_total_bytes": float(rn.metrics.total_bytes.mean()),
            "parity": parity})
    return results


def tpu_sweep(fast: bool = False):
    import jax
    from repro.core.fd import comm_bytes, fd_topk
    from repro.core.topology import measure_comm_bytes
    from repro.launch.mesh import make_host_mesh
    results = []
    mesh = make_host_mesh(model=len(jax.devices()))
    n_dev_real = dict(mesh.shape)["model"]
    n_model = 8                         # byte models at the deploy scale
    n_local = 4096
    ks = (20,) if fast else (8, 20)
    for schedule in ("halving", "doubling", "ring"):
        for k in ks:
            fn = jax.jit(lambda s, k=k, schedule=schedule: fd_topk(
                s, k, mesh, "model", schedule=schedule,
                batch_axes=("data",)))
            scores = jax.random.normal(jax.random.PRNGKey(0),
                                       (8, n_dev_real * n_local))
            fn(scores)[0].block_until_ready()
            t0 = time.perf_counter()
            for _ in range(10):
                out = fn(scores)
            jax.block_until_ready(out)
            us = (time.perf_counter() - t0) / 10 * 1e6
            results.append({
                "suite": "tpu", "schedule": schedule, "k": k,
                "n_dev": n_model, "n_local": n_local,
                "model_bytes": comm_bytes("fd", n_model, n_local, k,
                                          schedule=schedule),
                "measured_bytes": measure_comm_bytes(
                    "fd", n_model, n_local, k, schedule=schedule),
                "wall_us_per_call": us,
            })
    return results


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def collect(fast: bool = False) -> dict:
    import jax
    return {
        "meta": {"created_unix": time.time(), "fast": fast,
                 "jax": jax.__version__, "numpy": np.__version__},
        "results": (sim_sweep(fast) + speedup_bench(fast)
                    + plan_cache_bench(fast) + jax_backend_bench(fast)
                    + jax_churn_bench(fast) + precision_bench(fast)
                    + precision_scale_bench(fast) + topology_sweep(fast)
                    + tpu_sweep(fast)),
    }


def suite_rows():
    """benchmarks.run contract: (name, value, derived) rows (fast mode)."""
    data = collect(fast=True)
    rows = []
    for r in data["results"]:
        if r["suite"] == "sim":
            tag = (f"multi_query/sim/{r['policy']}/n={r['n_peers']}"
                   f"/k={r['k']}")
            rows.append((f"{tag}/qps", r["queries_per_s"],
                         f"{r['n_queries']}x{r['n_trials']} batch"))
            rows.append((f"{tag}/bytes", r["mean_total_bytes"],
                         "mean per query"))
        elif r["suite"] == "speedup":
            rows.append(("multi_query/speedup_vs_loop", r["speedup"],
                         "acceptance: >= 10x"))
        elif r["suite"] == "plan_cache":
            rows.append(("multi_query/plan_cache_speedup", r["speedup"],
                         "warm NetworkPlan vs cold; acceptance: > 1x"))
        elif r["suite"] == "jax_backend":
            rows.append((f"multi_query/jax_backend/n={r['n_peers']}"
                         "/speedup", r["speedup"],
                         "jitted engine vs scalar reference; "
                         "acceptance: >= 3x"))
            rows.append((f"multi_query/jax_backend/n={r['n_peers']}"
                         "/vs_batch_numpy", r["vs_batch_numpy"],
                         "jitted engine vs vectorized numpy backend"))
        elif r["suite"] == "jax_churn":
            rows.append((f"multi_query/jax_churn/n={r['n_peers']}"
                         f"/lt={r['lifetime_s']:g}/speedup", r["speedup"],
                         "jitted churn sweep vs scalar reference; "
                         "acceptance: >= 3x"))
        elif r["suite"] == "precision":
            tag = (f"multi_query/precision/{r['precision']}"
                   f"/n={r['n_peers']}")
            rows.append((f"{tag}/vs_f64", r["speedup_vs_f64"],
                         f"tol_ok={r['tol_ok']} recall={r['recall']:.3f}"
                         " (acceptance: tolerance contract)"))
        elif r["suite"] == "precision_scale":
            rows.append((f"multi_query/precision_scale/n={r['n_peers']}"
                         "/run_s", r["run_s"],
                         f"int32 plan, f32 sweep; tol_ok={r['tol_ok']}"))
        elif r["suite"] == "topology_sweep":
            tag = (f"multi_query/topology_sweep/{r['topology']}"
                   f"/n={r['n_peers']}")
            rows.append((f"{tag}/m_bw", r["mean_m_bw"],
                         f"{r['latency_model']} latency; parity="
                         f"{r['parity']} (acceptance: parity)"))
            rows.append((f"{tag}/response_s", r["mean_response_s"],
                         "mean per query"))
        else:
            rows.append((f"multi_query/tpu/{r['schedule']}/k={r['k']}"
                         "/bytes", r["model_bytes"],
                         f"measured={r['measured_bytes']}"))
    return rows


ALL = {"multi_query": suite_rows}


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: smaller sweeps, fewer reps")
    ap.add_argument("--out", default="BENCH_multi_query.json")
    args = ap.parse_args()
    enable_compile_cache()
    data = collect(fast=args.fast)
    with open(args.out, "w") as f:
        json.dump(data, f, indent=2)
    sp = [r for r in data["results"] if r["suite"] == "speedup"][0]
    pc = [r for r in data["results"] if r["suite"] == "plan_cache"][0]
    jx = [r for r in data["results"] if r["suite"] == "jax_backend"][0]
    ch = [r for r in data["results"] if r["suite"] == "jax_churn"]
    churn = "; ".join(f"lt={r['lifetime_s']:g}s {r['speedup']:.1f}x"
                      for r in ch)
    ts = [r for r in data["results"] if r["suite"] == "topology_sweep"]
    topo = ", ".join(f"{r['topology']}({r['n_peers'] // 1000}k)"
                     for r in ts)
    pr = [r for r in data["results"] if r["suite"] == "precision"]
    prec = "; ".join(f"{r['precision']} {r['speedup_vs_f64']:.2f}x "
                     f"tol_ok={r['tol_ok']}" for r in pr)
    ps = [r for r in data["results"]
          if r["suite"] == "precision_scale"][0]
    print(f"wrote {args.out}: {len(data['results'])} results; "
          f"speedup_vs_loop={sp['speedup']:.1f}x; "
          f"plan_cache warm/cold={pc['speedup']:.2f}x; "
          f"jax_backend {jx['speedup']:.1f}x vs reference "
          f"({jx['vs_batch_numpy']:.2f}x vs batch numpy, "
          f"n={jx['n_peers']}); jax_churn {churn}; "
          f"precision {prec}; 1M-peer int32+f32 "
          f"run_s={ps['run_s']:.2f}; topology_sweep parity on {topo}")


if __name__ == "__main__":
    main()
