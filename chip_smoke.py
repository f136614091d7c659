"""Chip smoke test: the FD top-k query service end to end on a TPU.

    python chip_smoke.py              # one chip: the served path
    python chip_smoke.py --chips 4    # four chips: the cross-chip paths

One chip (the default) drives the normal served path once at the size
of the repo's ``jax_backend`` / ``jax_churn`` suites: a 100k-peer
``ba`` overlay built from ``--seed`` under the paper's Table-1
parameters (``SimParams()``: k=20, N(200 ms) links, 56 kbps, 1000-20000
tuples per peer), a ``QueryServer`` in front of ``SimEngine(backend=
"jax")``, warmed on a fixed origin set, answering bursts of
``fd-dynamic``, ``fd-st1+2``, ``cn`` and churned ``fd-dynamic``
requests at f64 and at f32.  Every answer is checked against the numpy
backend on the same specs: f64 under the chip's f64 contract (see
``f64_diffs``), f32 under the tolerance contract of
``repro.engine.precision`` and with every reported owner holding the
score reported for it (``owners_hold``).

``--chips 4`` runs only what exists across chips: the ``DeviceEngine``
collectives over a 4-device ``model`` mesh (fd under the halving,
doubling and ring schedules with the Pallas local top-k, fd halving
with XLA's top_k, cn and cn-star) on 2^20 f32 scores per device against
``topk_ref`` of the whole vector, the ``fd_topk_gather`` rows against the same rows
gathered directly, and ``SimEngine(backend="jax", shard=True)`` on the
100k overlay against the numpy backend.

The script needs a TPU: with none it exits non-zero before printing a
result.  It never sets ``JAX_PLATFORMS``, runs in one process, and
keeps JAX's compile cache where ``repro.compile_cache`` says.  Every
phase prints its numbers; any failed check fails the run.  The last
line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

K = 20
N_PEERS = 100_000
ORIGINS = (0, 1)              # the fixed origin set the server is warm on
# requests per burst = the server's max_batch: each burst splits into
# one 4-entry sweep per origin.  A 100k-peer f64 sweep holds ~0.3 GB of
# temporaries per entry (jnp path, compiled for v5e), so a 4-entry
# bucket stays near 1.3 GB of the chip's 16 GB.
MAX_BATCH = 8
POLICIES = ("fd-dynamic", "fd-st1+2", "cn", "fd-dynamic@600")
PRECISIONS = ("f64", "f32")
SCORES_PER_DEVICE = 1 << 20   # --chips 4: f32 scores on each device
ROW_DIM = 8                   # --chips 4: width of the gathered table
F64_TIME_RTOL = 1e-12         # emulated-f64 sums over a tree's depth


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info(count: int) -> dict:
    """The device as JAX reports it; exit unless it is ``count`` TPUs."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX reports "
                         f"{info['platform']!r}); nothing was run")
    if info["count"] < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX "
                         f"reports {info['count']}")
    return info


def policy(name: str):
    from repro.engine import get_policy
    base, _, life = name.partition("@")
    pol = get_policy(base)
    return pol.variant(lifetime_mean_s=float(life)) if life else pol


def bit_diffs(got, ref) -> list:
    """What differs bit for bit between two sim results: the top-k
    values, the owners, and each per-entry metric (empty when none)."""
    from repro.engine.sim import _ALL_BM_FIELDS
    bad = []
    if not np.array_equal(got.values, ref.values):
        bad.append("values")
    if not np.array_equal(got.indices, ref.indices):
        bad.append("owners")
    return bad + [f for f in _ALL_BM_FIELDS
                  if not np.array_equal(getattr(got.metrics, f),
                                        getattr(ref.metrics, f))]


def tolerance(got, ref) -> dict:
    """The f32 tolerance contract of ``repro.engine.precision``: top-k
    owner recall (when the scores are separated) and positional score
    rtol against the numpy backend's f64 answer."""
    from repro.engine.precision import check_tolerance
    return check_tolerance("f32", got.values.reshape(-1, K),
                           got.indices.reshape(-1, K),
                           ref.values.reshape(-1, K),
                           ref.indices.reshape(-1, K)).summary()


def local_scores(n: int, params, seed: int) -> np.ndarray:
    """Each peer's local top-k scores, (n, k), for one independently
    seeded entry: the numpy draws both backends consume."""
    from repro.p2psim.simulate import _precompute_draws
    return _precompute_draws(np.zeros(1, np.int64), [seed], n, params,
                             "fd", "basic", math.inf, True).scores[0]


def owners_hold(got, scores32: np.ndarray) -> bool:
    """Every reported (owner, score) is one of that owner's own tuples
    at f32, and no pair is reported more often than the owner holds it.

    At 100k peers the f32 top k is one tie plateau (the ~30 largest of
    ~1e9 scores all round to 1.0), so which owners surface is free and
    recall against numpy says nothing; this check does not depend on
    the tie order and fails when a merge copies one owner over another.
    """
    v = got.values.reshape(-1)
    o = got.indices.reshape(-1)
    live = np.isfinite(v)
    pairs = collections.Counter(zip(o[live].tolist(), v[live].tolist()))
    return all(np.count_nonzero(scores32[own] == val) >= c
               for (own, val), c in pairs.items())


def f64_diffs(got, ref) -> list:
    """What breaks the f64 contract on TPU (empty when none).

    XLA emulates float64 on the TPU with about 49 significant bits: a
    value is rounded when it reaches the device, and sums carry a
    relative error near 1e-15.  The contract: owners, message and byte
    counts and accuracy equal the numpy backend's bit for bit; the top-k
    values equal numpy's values as rounded on upload where the sweep
    carried them through the device (FD), and numpy's own values where
    they never left the host (CN / CN*); response times agree within
    ``F64_TIME_RTOL``.  Where float64 is native all of it is bit-exact.
    """
    import jax
    from repro.engine.sim import _ALL_BM_FIELDS
    with jax.enable_x64():
        uploaded = np.asarray(jax.device_put(ref.values))
    bad = []
    if not (np.array_equal(got.values, ref.values)
            or np.array_equal(got.values, uploaded)):
        bad.append("values")
    if not np.array_equal(got.indices, ref.indices):
        bad.append("owners")
    for f in _ALL_BM_FIELDS:
        g, r = getattr(got.metrics, f), getattr(ref.metrics, f)
        if not (np.allclose(g, r, rtol=F64_TIME_RTOL, atol=0)
                if f == "response_time_s" else np.array_equal(g, r)):
            bad.append(f)
    return bad


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")


def one_chip(args) -> None:
    from repro.engine import QueryServer, QuerySpec, ServerConfig, SimEngine
    from repro.p2psim import SimParams, build_topology

    t0 = time.perf_counter()
    params = SimParams(seed=args.seed)
    top = build_topology("ba", N_PEERS, seed=args.seed)
    jx = SimEngine(top, params, backend="jax", validate_precision=False)
    ref_engine = SimEngine(jx.plan, params)          # numpy, same plan
    log(f"build: ba n={top.n} edges={top.n_edges} "
        f"{time.perf_counter() - t0:.3f}s")
    server = QueryServer(jx, ServerConfig(
        max_queue=4 * MAX_BATCH, max_batch=MAX_BATCH, batch_window_s=0.25,
        default_timeout_s=900.0))
    per_origin = MAX_BATCH // len(ORIGINS)

    # warm: one run per (precision, policy) traces exactly the per-origin
    # bucket the bursts below dispatch
    for prec in PRECISIONS:
        for name in POLICIES:
            t0 = time.perf_counter()
            res = server.warm(QuerySpec(
                origins=ORIGINS, n_trials=per_origin, seed=args.seed,
                rng="independent", precision=prec), policy(name))
            log(f"warm {prec} {name}: compile_s={res.compile_s:.3f} "
                f"traces={res.extras.get('jax_traces', 0)} "
                f"wall_s={time.perf_counter() - t0:.3f}")

    def burst(prec):
        return [QuerySpec(origins=(ORIGINS[i % len(ORIGINS)],),
                          seed=args.seed + 1000 + i, rng="independent",
                          precision=prec)
                for i in range(MAX_BATCH)]

    served = {}
    with server:
        for prec in PRECISIONS:
            for name in POLICIES:
                t0 = time.perf_counter()
                handles = [server.submit(s, policy(name))
                           for s in burst(prec)]
                res = [h.result() for h in handles]
                traces = sum(r.extras.get("jax_traces", 0) / r.batch_size
                             for r in res)
                compile_s = sum(r.compile_s / r.batch_size for r in res)
                used = {r.backend_used for r in res}
                log(f"serve {prec} {name}: requests={len(res)} "
                    f"batch_sizes={sorted({r.batch_size for r in res})} "
                    f"compile_s={compile_s:.3f} traces={traces:g} "
                    f"backend_used={sorted(used)} "
                    f"wall_s={time.perf_counter() - t0:.3f}")
                if used != {"sim-jax"}:
                    raise AssertionError(f"{prec} {name}: backend_used "
                                         f"{used}, expected sim-jax")
                served[prec, name] = res
        m = server.metrics()
    total = len(PRECISIONS) * len(POLICIES) * MAX_BATCH
    log(f"server: submitted={m.submitted} served={m.served} shed={m.shed} "
        f"timed_out={m.timed_out} failed={m.failed} "
        f"p50_s={m.latency.p50_s:.6f} p99_s={m.latency.p99_s:.6f} "
        f"(latency for information only)")
    if (m.served, m.shed, m.timed_out, m.failed) != (total, 0, 0, 0):
        raise AssertionError(f"expected {total} served and none shed, "
                             f"timed out or failed: {m.as_dict()}")

    failures = []
    scores32 = [local_scores(top.n, params, s.seed).astype(np.float32)
                for s in burst("f32")]
    for name in POLICIES:
        specs = [dataclasses.replace(s, precision=None)
                 for s in burst("f64")]
        t0 = time.perf_counter()
        refs = ref_engine.run_many(specs, policy(name))
        numpy_s = time.perf_counter() - t0
        f64 = [f64_diffs(served["f64", name][i], ref)
               for i, ref in enumerate(refs)]
        f32 = [tolerance(served["f32", name][i], ref)
               for i, ref in enumerate(refs)]
        held = [owners_hold(served["f32", name][i], scores32[i])
                for i in range(len(refs))]
        n_exact = sum(not bit_diffs(served["f64", name][i], ref)
                      for i, ref in enumerate(refs))
        n64 = sum(not d for d in f64)
        n32 = sum(r["ok"] and h for r, h in zip(f32, held))
        log(f"check {name}: f64 contract ok {n64}/{len(refs)} "
            f"(breaking: {sorted({f for d in f64 for f in d})}; "
            f"bit-exact {n_exact}/{len(refs)}); "
            f"f32 ok {n32}/{len(refs)} (tolerance "
            f"{sum(r['ok'] for r in f32)}, owners hold their scores "
            f"{sum(held)}; separated={all(r['separated'] for r in f32)} "
            f"min_recall={min(r['min_recall'] for r in f32)} "
            f"mean_recall={float(np.mean([r['recall'] for r in f32]))!r} "
            f"max_rtol={max(r['max_rtol'] for r in f32)!r}) "
            f"numpy_s={numpy_s:.3f}")
        if n64 < len(refs) or n32 < len(refs):
            failures.append(name)
    import jax
    log(f"memory: {peak_bytes(jax.devices()[0])}")
    if failures:
        raise AssertionError(f"results differ from the numpy backend "
                             f"beyond their contract: {failures}")


def four_chips(args) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import jaxcompat
    from repro.engine import DeviceEngine, QuerySpec, SimEngine
    from repro.engine import sim_jax
    from repro.kernels.topk import topk_ref
    from repro.p2psim import SimParams, build_topology

    mesh = jaxcompat.make_mesh((4,), ("model",))
    n = 4 * SCORES_PER_DEVICE
    kx, kr = jax.random.split(jax.random.key(args.seed))
    scores = jax.jit(
        lambda key: jax.random.normal(key, (n,), jnp.float32),
        out_shardings=NamedSharding(mesh, P("model")))(kx)
    table = jax.jit(
        lambda key: jax.random.normal(key, (n, ROW_DIM), jnp.float32),
        out_shardings=NamedSharding(mesh, P("model", None)))(kr)
    host = np.asarray(scores)
    ref_v, ref_i = (np.asarray(a) for a in topk_ref(jnp.asarray(host), K))
    assert np.array_equal(host[ref_i], ref_v)
    ref_rows = np.asarray(table)[ref_i]
    spec = QuerySpec(k=K)

    def copies(a):
        return [np.asarray(s.data) for s in a.addressable_shards]

    def check(tag, res, rows=False):
        """Every device's copy of the answer is topk_ref's, ties and
        all (the collectives rank by score, then global index)."""
        devs = len(res.values.sharding.device_set)
        ok = (all(np.array_equal(v, ref_v) for v in copies(res.values))
              and all(np.array_equal(i, ref_i)
                      for i in copies(res.indices))
              and (not rows or all(np.array_equal(r, ref_rows)
                                   for r in copies(res.rows))))
        log(f"device {tag}: match={ok} devices={devs} "
            f"compile_s={res.compile_s:.3f} run_s={res.run_s:.6f}")
        if not ok or devs != 4:
            raise AssertionError(f"{tag}: result differs from topk_ref "
                                 f"or does not span the 4-device mesh")

    # the Pallas local top-k compiles in a second; XLA's top_k over a
    # 2^20-wide shard takes ~25 s per program, so the jnp variant runs
    # on one schedule
    for schedule in ("halving", "doubling", "ring"):
        check(f"fd {schedule} pallas=True",
              DeviceEngine(mesh, schedule=schedule, use_pallas=True).run(
                  spec, "fd-dynamic", scores=scores))
    check("fd halving pallas=False",
          DeviceEngine(mesh).run(spec, "fd-dynamic", scores=scores))
    check("fd_topk_gather halving",
          DeviceEngine(mesh).run(spec, "fd-dynamic", scores=scores,
                                 rows=table), rows=True)
    for name in ("cn", "cn-star"):
        check(name, DeviceEngine(mesh).run(spec, name, scores=scores))

    params = SimParams(seed=args.seed)
    top = build_topology("ba", N_PEERS, seed=args.seed)
    sharded = SimEngine(top, params, backend="jax", shard=True)
    ref_engine = SimEngine(sharded.plan, params)
    for name in ("fd-dynamic",):
        qs = QuerySpec(origins=ORIGINS[:1], n_trials=MAX_BATCH,
                       seed=args.seed, rng="independent")
        t0 = time.perf_counter()
        got = sharded.run(qs, policy(name))
        wall = time.perf_counter() - t0
        ref = ref_engine.run(qs, policy(name))
        bad = f64_diffs(got, ref)
        log(f"sharded sweep {name}: entries={MAX_BATCH} "
            f"compile_s={got.compile_s:.3f} wall_s={wall:.3f} "
            f"backend_used={got.backend_used} f64 contract "
            f"ok={not bad} (breaking: {bad}; bit diffs: "
            f"{bit_diffs(got, ref)})")
        if bad or got.backend_used != "sim-jax":
            raise AssertionError(f"sharded sweep {name} breaks the f64 "
                                 f"contract against the numpy backend")
    if sim_jax._sharded_fd_sweep.cache_info().currsize == 0:
        raise AssertionError("shard=True never built the sharded sweep")
    log("memory: " + "; ".join(peak_bytes(d) for d in jax.devices()))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit("chip_smoke: run from a checkout of the repo "
                         f"({SRC}/repro not found)")
    info = device_info(args.chips)
    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    log(f"device: {info['kind']} x{info['count']} ({info['platform']}); "
        f"compile cache {cache}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args)
    log(f"total_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
