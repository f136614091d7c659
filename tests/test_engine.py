"""Unified engine API (ISSUE 2): QuerySpec + Policy registry + compiled
NetworkPlan across the sim and device backends.

  * every registered policy runs through SimEngine with bit-exact parity
    against the scalar ``run_query_reference`` (shared-stream batch of
    one AND independent streams) and against the legacy shims;
  * the NetworkPlan is cached across ``run`` calls (no BFS /
    edge-mask recompute) without changing a single bit of output;
  * DeviceEngine matches ``fd_topk_gather`` on all three schedules and
    ``fd_topk`` for the CN / CN* baselines.
"""
import dataclasses
import inspect
import math

import numpy as np
import pytest

from repro.engine import (NetworkPlan, Policy, QuerySpec, SimEngine,
                          TopKResult, available_policies, get_policy,
                          policy_from_legacy, register_policy)
from repro.p2psim import (SimParams, barabasi_albert, run_queries,
                          run_query, run_query_reference,
                          run_statistics_heuristic, waxman)

TOP = barabasi_albert(220, m=2, seed=7)
PA = SimParams(seed=11)

STANDARD = [n for n in available_policies() if n != "fd-stats"]


def _legacy_kwargs(pol: Policy) -> dict:
    kw = dict(algorithm=pol.algorithm, strategy=pol.strategy,
              dynamic=pol.dynamic)
    if not math.isinf(pol.lifetime_mean_s):
        kw["lifetime_mean_s"] = pol.lifetime_mean_s
    return kw


# --------------------------------------------------------------------------
# SimEngine parity: every registered policy, both RNG modes
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", STANDARD)
def test_sim_engine_shared_batch_of_one_is_reference(name):
    pol = get_policy(name)
    engine = SimEngine(TOP)
    for origin, seed in ((0, 0), (17, 11)):
        pa = SimParams(seed=seed)
        met, _ = run_query_reference(TOP, origin, pa, **_legacy_kwargs(pol))
        res = engine.run(QuerySpec(origins=(origin,), seed=seed), name)
        assert isinstance(res, TopKResult)
        assert res.backend == "sim" and res.policy == name
        assert res.query_metrics(0, 0) == met


@pytest.mark.parametrize("name", STANDARD)
def test_sim_engine_independent_streams_entrywise_reference(name):
    pol = get_policy(name)
    origins = (0, 9, 9, 41)
    engine = SimEngine(TOP, PA)
    res = engine.run(QuerySpec(origins=origins, n_trials=2,
                               rng="independent"), name)
    for q, o in enumerate(origins):
        for t in range(2):
            met, _ = run_query_reference(
                TOP, o, dataclasses.replace(PA, seed=PA.seed + q * 2 + t),
                **_legacy_kwargs(pol))
            assert res.query_metrics(q, t) == met, (name, q, t)


@pytest.mark.parametrize("name", STANDARD)
def test_sim_engine_matches_legacy_shims(name, monkeypatch):
    monkeypatch.setenv("REPRO_LEGACY_API", "1")   # retired shims re-enabled
    pol = get_policy(name)
    engine = SimEngine(TOP, PA)
    res = engine.run(QuerySpec(origins=(3, 12), n_trials=2), name)
    bm = run_queries(TOP, [3, 12], PA, 2, **_legacy_kwargs(pol))
    for f in ("n_reached", "m_fw", "m_bw", "m_rt", "b_fw", "b_bw", "b_rt",
              "response_time_s", "accuracy"):
        np.testing.assert_array_equal(getattr(res.metrics, f),
                                      getattr(bm, f), err_msg=f)
    # the scalar shim is a batch of ONE (shared stream) over the engine
    one = engine.run(QuerySpec(origins=(3,)), name)
    met, _ = run_query(TOP, 3, PA, **_legacy_kwargs(pol))
    assert one.query_metrics(0, 0) == met


def test_churn_policy_variant_parity():
    pol = get_policy("fd-dynamic").variant(lifetime_mean_s=45.0)
    res = SimEngine(TOP, PA).run(QuerySpec(origins=(0,)), pol)
    met, _ = run_query_reference(TOP, 0, PA, lifetime_mean_s=45.0)
    assert res.query_metrics(0, 0) == met


def test_spec_k_and_explicit_seeds_override():
    seeds = np.array([[101, 202], [303, 404]])
    spec = QuerySpec(origins=(0, 9), n_trials=2, k=7, seeds=seeds)
    assert spec.rng == "independent"          # implied by seeds
    res = SimEngine(TOP, PA).run(spec, "fd-st1+2")
    assert res.k == 7
    for q, o in enumerate((0, 9)):
        for t in range(2):
            met, _ = run_query_reference(
                TOP, o, dataclasses.replace(PA, k=7, seed=int(seeds[q, t])),
                strategy="st1+2", dynamic=False)
            assert res.query_metrics(q, t) == met


# --------------------------------------------------------------------------
# SimEngine(backend="jax"): jitted sweeps, same bits (ISSUE 3)
# --------------------------------------------------------------------------

JTOP = barabasi_albert(96, m=2, seed=3)      # small: keeps jit compiles fast
_PARITY_FIELDS = ("n_reached", "n_edges_pq", "m_fw", "m_bw", "m_rt",
                  "b_fw", "b_bw", "b_rt", "response_time_s", "accuracy")


def _assert_metrics_equal(a, b, msg):
    for f in _PARITY_FIELDS:
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                      err_msg=f"{msg}: {f}")


@pytest.mark.parametrize("name", STANDARD)
def test_jax_backend_bit_exact_all_policies(name):
    """backend="jax" == numpy backend in EVERY rng mode (same draws, same
    sweep results bit-for-bit), and == the scalar reference wherever the
    numpy backend is (shared batch of one, independent streams)."""
    pol = get_policy(name)
    en = SimEngine(JTOP, PA)
    ej = SimEngine(JTOP, PA, backend="jax")
    assert ej.backend == "sim-jax"
    # shared batch of one == scalar reference
    met, _ = run_query_reference(JTOP, 5, SimParams(seed=2),
                                 **_legacy_kwargs(pol))
    res = ej.run(QuerySpec(origins=(5,), seed=2), name)
    assert res.backend == "sim-jax" and res.query_metrics(0, 0) == met
    # independent streams: entry-wise reference parity
    spec = QuerySpec(origins=(0, 7, 7), n_trials=2, rng="independent")
    rj = ej.run(spec, name)
    for q, o in enumerate((0, 7, 7)):
        for t in range(2):
            met, _ = run_query_reference(
                JTOP, o, dataclasses.replace(PA, seed=PA.seed + q * 2 + t),
                **_legacy_kwargs(pol))
            assert rj.query_metrics(q, t) == met, (name, q, t)
    # shared stream, batch > 1: full cross-backend equality
    spec = QuerySpec(origins=(1, 8), n_trials=3)
    _assert_metrics_equal(ej.run(spec, name).metrics,
                          en.run(spec, name).metrics, name)


@pytest.mark.parametrize("name", ["fd-dynamic", "fd-dynamic@600"])
def test_jax_urgent_pass_reads_device_arrival_times(name):
    """The urgent-list pass judges a child late by comparing its list's
    arrival at the parent with the parent's send time, both as the sweep
    computed them.  Re-adding the arrival on the host in float64 made
    the child that released each waiting parent look late whenever the
    device summed in lower precision (f32 here; TPU's emulated f64 too),
    adding urgent messages the f64 run does not have."""
    base, _, life = name.partition("@")
    pol = get_policy(base)
    if life:
        pol = pol.variant(lifetime_mean_s=float(life))
    ej = SimEngine(JTOP, PA, backend="jax", validate_precision=False)
    spec = QuerySpec(origins=(0, 3), n_trials=4, rng="independent")
    r64 = ej.run(spec, pol)
    r32 = ej.run(dataclasses.replace(spec, precision="f32"), pol)
    np.testing.assert_array_equal(r32.metrics.m_bw, r64.metrics.m_bw)


# link latencies and wait budgets under which late children are common
# and many of their urgent lists still reach the origin before its merge
# is done: more than one gather chunk of accepted lists per origin
URGENT_PA = SimParams(seed=11, latency_mean_s=0.2, latency_var=0.3 ** 2,
                      t_qsnd_s=0.2, t_slsnd_s=0.3)


@pytest.mark.parametrize("name", ["fd-dynamic", "fd-dynamic@25"])
def test_jax_urgent_rows_bit_exact(name, monkeypatch):
    """The jax backend copies back only the origin's merged list and
    fetches the lists of the urgent children the origin accepts from
    the device, in chunks; the numpy backend reads the same rows from
    its full arrays.  Both fold them in through the one shared epilogue
    and agree bit for bit, in both rng modes, under churn and reroute
    too, with several chunks per origin."""
    from repro.engine import sim_jax
    base, _, life = name.partition("@")
    pol = get_policy(base)
    if life:
        pol = pol.variant(lifetime_mean_s=float(life))
    rows = []
    accept = sim_jax._accept_urgent_origin

    def spy(org_v, org_o, ue, cv, co, k):
        rows.append(len(ue))
        accept(org_v, org_o, ue, cv, co, k)
    monkeypatch.setattr(sim_jax, "_accept_urgent_origin", spy)
    en = SimEngine(JTOP, URGENT_PA)
    ej = SimEngine(JTOP, URGENT_PA, backend="jax")
    for rng in ("independent", "shared"):
        spec = QuerySpec(origins=(0, 17), n_trials=16, rng=rng)
        rj, rn = ej.run(spec, pol), en.run(spec, pol)
        _assert_metrics_equal(rj.metrics, rn.metrics, f"{name} {rng}")
        np.testing.assert_array_equal(rj.values, rn.values)
        np.testing.assert_array_equal(rj.indices, rn.indices)
    assert len(rows) == 4 and max(rows) > sim_jax.URGENT_CHUNK, rows


def test_jax_backend_pallas_kernel_path():
    """use_pallas=True routes every pairwise merge through the Pallas
    bitonic kernel (interpret mode off-TPU) — same bits as the default
    fused-jnp network and the numpy backend."""
    pa = SimParams(seed=4, k=8)
    spec = QuerySpec(origins=(0, 3), n_trials=2)
    rn = SimEngine(JTOP, pa).run(spec, "fd-dynamic")
    rp = SimEngine(JTOP, pa, backend="jax", use_pallas=True).run(
        spec, "fd-dynamic")
    _assert_metrics_equal(rp.metrics, rn.metrics, "pallas")


@pytest.mark.parametrize("name", STANDARD)
def test_jax_backend_churn_bit_exact_all_policies(name):
    """Finite ``lifetime_mean_s`` runs IN the jitted sweep (no numpy
    fallback, asserted via ``backend_used``) and stays bit-exact: ==
    the numpy backend in every rng mode, == the scalar reference
    wherever numpy is (shared batch of one, independent streams)."""
    pol = get_policy(name).variant(lifetime_mean_s=25.0)
    en = SimEngine(JTOP, PA)
    ej = SimEngine(JTOP, PA, backend="jax")
    kw = _legacy_kwargs(pol)
    # shared batch of one == scalar reference, executed on the jax path
    met, _ = run_query_reference(JTOP, 5, SimParams(seed=2), **kw)
    res = ej.run(QuerySpec(origins=(5,), seed=2), pol)
    assert res.backend_used == "sim-jax"          # no silent fallback
    assert res.query_metrics(0, 0) == met
    # independent streams: entry-wise reference parity under churn
    spec = QuerySpec(origins=(0, 7), n_trials=2, rng="independent")
    rj = ej.run(spec, pol)
    assert rj.backend_used == "sim-jax"
    for q, o in enumerate((0, 7)):
        for t in range(2):
            met, _ = run_query_reference(
                JTOP, o, dataclasses.replace(PA, seed=PA.seed + q * 2 + t),
                **kw)
            assert rj.query_metrics(q, t) == met, (name, q, t)
    # shared stream, batch > 1: full cross-backend equality
    spec = QuerySpec(origins=(1, 8), n_trials=3)
    _assert_metrics_equal(ej.run(spec, pol).metrics,
                          en.run(spec, pol).metrics, name)


def test_jax_backend_no_churn_fallback_and_stats_warns_once():
    """Churn executes on the jax path (the old transparent numpy
    fallback is gone); the one remaining fallback — fd-stats — is
    recorded on ``backend_used`` and warned about at most ONCE per
    engine, however many runs hit it."""
    import warnings as _warnings
    ej = SimEngine(JTOP, PA, backend="jax")
    en = SimEngine(JTOP, PA)
    pol = get_policy("fd-dynamic").variant(lifetime_mean_s=30.0)
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")           # churn must NOT warn
        rj = ej.run(QuerySpec(origins=(0,)), pol)
    assert rj.backend == rj.backend_used == "sim-jax"
    assert (rj.query_metrics(0, 0)
            == en.run(QuerySpec(origins=(0,)), pol).query_metrics(0, 0))
    with _warnings.catch_warnings(record=True) as seen:
        _warnings.simplefilter("always")
        rs = ej.run(QuerySpec(origins=(0,)), "fd-stats")
        ej.run(QuerySpec(origins=(0,)), "fd-stats")   # second run: silent
    assert rs.backend == "sim-jax" and rs.backend_used == "sim"
    fallback_warns = [w for w in seen
                      if "numpy reference path" in str(w.message)]
    assert len(fallback_warns) == 1
    rn = en.run(QuerySpec(origins=(0,)), "fd-stats")
    assert rn.backend_used == rn.backend == "sim"     # numpy: no warning
    assert rs.extras["metrics_full"] == rn.extras["metrics_full"]
    assert rs.extras["accuracy"] == rn.extras["accuracy"]


# --------------------------------------------------------------------------
# churn edge cases (§4/§5.4): the scenarios the jitted sweep must nail
# --------------------------------------------------------------------------

def _edges_topology(n, edges):
    from repro.p2psim.graph import Topology
    adj = [set() for _ in range(n)]
    for u, v in edges:
        adj[u].add(v)
        adj[v].add(u)
    return Topology(n, [np.array(sorted(a), np.int32) for a in adj],
                    "test")


# a 5-level tree: levels {0} {1,2} {3,4,5} {6,7,8} {9,10} — small enough
# to scan seeds against the scalar reference, deep enough for reroute
# cascades (grandchildren exist at three levels)
CHURN_TREE = _edges_topology(
    11, [(0, 1), (0, 2), (1, 3), (1, 4), (2, 5), (3, 6), (4, 7), (5, 8),
         (6, 9), (7, 10)])


def _churn_reference(seed, lifetime):
    met, st = run_query_reference(
        CHURN_TREE, 0, SimParams(seed=seed), lifetime_mean_s=lifetime,
        return_state=True)
    dead = {int(v) for v in np.flatnonzero(st["reached"])
            if st["merged_scores"][v] is None}
    return met, st, dead


def test_churn_entire_level_dead_forces_reroute_cascade():
    """An ENTIRE depth level dies before sending: every level-2 list
    must reach the origin through §4.2 rerouting (dead parent ->
    grandparent), and both engine backends must reproduce the scalar
    reference bit-for-bit on that entry."""
    found = None
    for seed in range(500):
        met, st, dead = _churn_reference(seed, 2.5)
        lvl1 = {int(v) for v in np.flatnonzero(st["depth"] == 1)}
        lvl2 = {int(v) for v in np.flatnonzero(st["depth"] == 2)}
        if lvl1 and lvl1 <= dead and (lvl2 - dead):
            found = (seed, met, lvl2 - dead)
            break
    assert found is not None, "no full-level-dead seed found in range"
    seed, met, rerouted = found
    pol = get_policy("fd-dynamic").variant(lifetime_mean_s=2.5)
    spec = QuerySpec(origins=(0,), seed=seed)
    for backend in ("numpy", "jax"):
        res = SimEngine(CHURN_TREE, backend=backend).run(spec, pol)
        assert res.query_metrics(0, 0) == met, backend
    # the surviving level-2 lists were rerouted, not dropped: their
    # owners can only appear in the final list via the dead parent's
    # replacement path
    assert met.m_bw >= len(rerouted)


def test_churn_lifetime_shorter_than_one_hop_wait():
    """lifetime_mean_s far below a single hop's latency: every
    non-origin peer dies before its send time.  The origin is clamped
    immortal in the SHARED draws (the paper's originator waits out its
    own query), answers from its own k-list alone, and all backends
    agree bit-for-bit."""
    from repro.p2psim.simulate import _precompute_draws
    pa = SimParams(seed=3)
    lifetime = 0.01                     # hop latency alone is ~0.2 s
    draws = _precompute_draws(np.array([0]), [pa.seed], CHURN_TREE.n, pa,
                              "fd", "st1+2", lifetime, True)
    assert np.isinf(draws.death[0, 0])            # origin never dies
    assert np.isfinite(draws.death[0, 1:]).all()
    met, st, dead = _churn_reference(pa.seed, lifetime)
    reached = {int(v) for v in np.flatnonzero(st["reached"])}
    assert 0 not in dead and reached - {0} <= dead
    pol = get_policy("fd-dynamic").variant(lifetime_mean_s=lifetime)
    spec = QuerySpec(origins=(0,), seed=pa.seed)
    rj = SimEngine(CHURN_TREE, backend="jax").run(spec, pol)
    rn = SimEngine(CHURN_TREE).run(spec, pol)
    assert rj.query_metrics(0, 0) == met == rn.query_metrics(0, 0)
    assert int(rj.metrics.m_bw[0, 0]) == 0        # nobody lived to send
    # heavy churn must cost accuracy vs the static network
    static, _ = run_query_reference(CHURN_TREE, 0, SimParams(seed=3))
    assert met.accuracy < static.accuracy


def test_jax_backend_nonpow2_k_and_explicit_seeds():
    seeds = np.array([[11, 22], [33, 44]])
    spec = QuerySpec(origins=(0, 9), n_trials=2, k=7, seeds=seeds)
    res = SimEngine(JTOP, PA, backend="jax").run(spec, "fd-st1+2")
    for q, o in enumerate((0, 9)):
        for t in range(2):
            met, _ = run_query_reference(
                JTOP, o,
                dataclasses.replace(PA, k=7, seed=int(seeds[q, t])),
                strategy="st1+2", dynamic=False)
            assert res.query_metrics(q, t) == met


def test_jax_backend_validation_and_plan_sharing():
    with pytest.raises(ValueError):
        SimEngine(JTOP, backend="cuda")
    plan = NetworkPlan(JTOP)
    en = SimEngine(plan, PA)
    ej = SimEngine(plan, PA, backend="jax")
    spec = QuerySpec(origins=(2,))
    _assert_metrics_equal(ej.run(spec).metrics, en.run(spec).metrics,
                          "shared plan")
    assert ej.plan is en.plan is plan
    # the depth slices are compiled once and cached on the shared plan
    assert plan.cache_info()["depth_slices"] >= 1
    n_slices = plan.cache_info()["depth_slices"]
    ej.run(spec)
    assert plan.cache_info()["depth_slices"] == n_slices


# --------------------------------------------------------------------------
# fd-stats policy (two-round statistics heuristic)
# --------------------------------------------------------------------------

def test_fd_stats_policy_matches_legacy_and_reduces_traffic(monkeypatch):
    monkeypatch.setenv("REPRO_LEGACY_API", "1")   # retired shims re-enabled
    engine = SimEngine(TOP, PA)
    res = engine.run(QuerySpec(origins=(0,)),
                     get_policy("fd-stats").variant(z=0.8))
    m1, m2, red, acc = run_statistics_heuristic(TOP, 0, PA, 0.8)
    assert res.extras["metrics_full"] == m1
    assert res.extras["metrics_pruned"] == m2
    assert res.extras["comm_reduction"] == red
    assert res.extras["accuracy"] == acc
    assert res.query_metrics(0, 0) == m2      # metrics = pruned round
    assert red > 0.0 and acc > 0.5
    # the two reference rounds ran against the plan-resolved auto-TTL
    assert engine.plan.cache_info()["auto_ttls"] == 1
    with pytest.raises(ValueError):
        engine.run(QuerySpec(origins=(0, 1)), "fd-stats")
    # an explicit (1, 1) seeds grid selects the entry's RNG stream
    seeded = engine.run(QuerySpec(origins=(0,), seeds=[[42]]), "fd-stats")
    m1s, _, _, _ = run_statistics_heuristic(
        TOP, 0, dataclasses.replace(PA, seed=42), 0.8)
    assert seeded.extras["metrics_full"] == m1s
    with pytest.raises(ValueError):
        engine.run(QuerySpec(origins=(0,), seeds=[[1, 2]]), "fd-stats")


# --------------------------------------------------------------------------
# NetworkPlan caching
# --------------------------------------------------------------------------

def test_network_plan_reused_and_bit_identical():
    engine = SimEngine(TOP, PA)
    spec = QuerySpec(origins=(0, 5, 5), n_trials=2)
    r1 = engine.run(spec)
    cached = engine.plan.cache_info()["origin_statics"]
    assert cached == 2                        # two distinct origins
    r2 = engine.run(spec)
    assert engine.plan.cache_info()["origin_statics"] == cached
    for f in ("m_fw", "m_bw", "b_bw", "b_rt", "response_time_s",
              "accuracy"):
        np.testing.assert_array_equal(getattr(r1.metrics, f),
                                      getattr(r2.metrics, f))
    # cn needs the "basic" forward masks -> new cache entries, same BFS
    engine.run(spec, "cn")
    assert engine.plan.cache_info()["origin_statics"] == 2 * cached
    # warm results still match a cold engine bit-for-bit
    r3 = SimEngine(TOP, PA).run(spec)
    np.testing.assert_array_equal(r2.metrics.response_time_s,
                                  r3.metrics.response_time_s)


def test_plan_is_shareable_and_ttl_param_keyed():
    plan = NetworkPlan(TOP)
    e1 = SimEngine(plan, PA)
    e2 = SimEngine(plan, dataclasses.replace(PA, ttl=3))
    m_auto = e1.run(QuerySpec(origins=(0,))).query_metrics()
    m_ttl3 = e2.run(QuerySpec(origins=(0,))).query_metrics()
    assert e1.plan is e2.plan is plan
    assert m_ttl3.n_reached < m_auto.n_reached        # TTL 3 truncates
    ref, _ = run_query_reference(TOP, 0, dataclasses.replace(PA, ttl=3))
    assert m_ttl3 == ref
    assert plan.auto_ttl(0) == e1.plan._statics[
        (0, 0, "st1+2")].ttl          # resolved once, shared


def test_prepare_required():
    with pytest.raises(RuntimeError):
        SimEngine().run(QuerySpec())


# --------------------------------------------------------------------------
# registry / spec / legacy-kwarg mapping
# --------------------------------------------------------------------------

def test_registry_surface():
    assert set(available_policies()) == {
        "fd-basic", "fd-st1", "fd-st1+2", "fd-dynamic", "cn", "cn-star",
        "fd-stats"}
    with pytest.raises(KeyError):
        get_policy("fd-nope")
    with pytest.raises(ValueError):
        register_policy(Policy("cn", "cn"))
    pol = get_policy("fd-dynamic")
    assert get_policy(pol) is pol             # Policy passes through
    assert pol.variant(lifetime_mean_s=9.0).lifetime_mean_s == 9.0
    assert pol.lifetime_mean_s == math.inf    # variant is a copy


def test_policy_from_legacy_mapping():
    assert policy_from_legacy("fd", "st1+2", True).name == "fd-dynamic"
    assert policy_from_legacy("fd", "st1+2", False).name == "fd-st1+2"
    assert policy_from_legacy("fd", "basic", False).name == "fd-basic"
    assert policy_from_legacy("fd", "st1", False).name == "fd-st1"
    assert policy_from_legacy("cn").name == "cn"
    assert policy_from_legacy("cn_star").name == "cn-star"
    anon = policy_from_legacy("fd", "basic", True)    # no named member
    assert anon.algorithm == "fd" and anon.dynamic
    assert policy_from_legacy(
        "fd", lifetime_mean_s=60.0).lifetime_mean_s == 60.0


def test_query_spec_validation():
    with pytest.raises(ValueError):
        QuerySpec(rng="both")
    with pytest.raises(ValueError):
        QuerySpec(n_trials=0)
    with pytest.raises(ValueError):           # seeds shape mismatch
        SimEngine(TOP).run(QuerySpec(origins=(0,), n_trials=2,
                                     seeds=np.zeros((3, 3), np.int64)))


def test_no_shared_mutable_params_default(monkeypatch):
    # the old ``params: SimParams = SimParams()`` module-level instance
    # was shared across calls; defaults must now be None
    for fn in (run_query, run_queries, run_query_reference):
        assert inspect.signature(fn).parameters["params"].default is None
    monkeypatch.setenv("REPRO_LEGACY_API", "1")   # retired shims re-enabled
    m1, _ = run_query(TOP, 0)
    m2, _ = run_query(TOP, 0)
    assert m1 == m2


def test_waxman_cross_check():
    wax = waxman(120, seed=3)
    engine = SimEngine(wax, PA)
    for name in ("fd-dynamic", "cn-star"):
        res = engine.run(QuerySpec(origins=(1,)), name)
        met, _ = run_query_reference(wax, 1, PA,
                                     **_legacy_kwargs(get_policy(name)))
        assert res.query_metrics(0, 0) == met


# --------------------------------------------------------------------------
# DeviceEngine: same surface over the shard_map collectives
# --------------------------------------------------------------------------

def test_device_engine_matches_fd_collectives(devices8):
    out = devices8("""
import jax, numpy as np
from repro.core.fd import fd_topk, fd_topk_gather
from repro.engine import DeviceEngine, QuerySpec, get_policy
from repro.jaxcompat import make_mesh

mesh = make_mesh((8,), ("model",))
scores = jax.random.normal(jax.random.PRNGKey(3), (2, 1024))
rows = jax.random.normal(jax.random.PRNGKey(6), (1024, 16))
spec = QuerySpec(k=20)
for sched in ("halving", "doubling", "ring"):
    eng = DeviceEngine(mesh, schedule=sched)
    res = eng.run(spec, "fd-dynamic", scores=scores, rows=rows)
    rv, ri, rr = fd_topk_gather(scores, rows, 20, mesh, "model",
                                schedule=sched)
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(res.indices), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(res.rows), np.asarray(rr))
    assert res.backend == "device" and res.extras["model_bytes"] > 0
    # compiled plan reuse: second run hits the cached jitted callable
    n = len(eng._compiled)
    res2 = eng.run(spec, "fd-dynamic", scores=scores, rows=rows)
    assert len(eng._compiled) == n
    np.testing.assert_array_equal(np.asarray(res2.values),
                                  np.asarray(res.values))
eng = DeviceEngine(mesh)
for pol, alg in (("cn", "cn"), ("cn-star", "cn_star")):
    res = eng.run(spec, pol, scores=scores)
    rv, ri = fd_topk(scores, 20, mesh, "model", algorithm=alg)
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(rv))
# every fd-* policy lowers to the same FD collective
ra = DeviceEngine(mesh).run(spec, "fd-basic", scores=scores)
rb = DeviceEngine(mesh).run(spec, "fd-dynamic", scores=scores)
np.testing.assert_array_equal(np.asarray(ra.values), np.asarray(rb.values))
try:
    eng.run(spec, "fd-stats", scores=scores)
    raise SystemExit("fd-stats must not lower to the device backend")
except ValueError:
    pass
try:
    eng.run(spec, "cn", scores=scores, rows=rows)
    raise SystemExit("gather path must be FD-only")
except ValueError:
    pass
print("DEVICE_ENGINE_OK")
""")
    assert "DEVICE_ENGINE_OK" in out


# --------------------------------------------------------------------------
# shard_map-sharded sim sweep + DeviceEngine precision (ISSUE 10)
# --------------------------------------------------------------------------

def test_sharded_sim_sweep_matches_numpy_bits(devices8):
    """``SimEngine(backend="jax", shard=True)`` partitions the entry
    batch over the device mesh via the jaxcompat shard_map layer and
    must keep the f64 bit contract — and the reduced-precision
    tolerance contract — intact across 8 devices."""
    out = devices8("""
import jax, numpy as np
from repro.engine import SimEngine, QuerySpec
from repro.p2psim import SimParams, barabasi_albert

assert jax.local_device_count() == 8
top = barabasi_albert(150, m=2, seed=3)
p = SimParams(k=5, seed=7)
spec = QuerySpec(origins=(0, 9, 23), n_trials=4, seed=7,
                 rng="independent")           # 12 entries over 8 devices
fields = ("m_fw", "m_bw", "m_rt", "b_fw", "b_bw", "b_rt",
          "response_time_s", "accuracy")
for pol in ("fd-basic", "fd-st1", "fd-dynamic"):
    rn = SimEngine(top, p).run(spec, pol)
    rs = SimEngine(top, p, backend="jax", shard=True).run(spec, pol)
    assert rs.backend_used == "sim-jax", pol
    for f in fields:
        np.testing.assert_array_equal(
            getattr(rn.metrics, f), getattr(rs.metrics, f),
            err_msg=f"shard {pol}: {f}")
rs32 = SimEngine(top, p, backend="jax", shard=True,
                 precision="f32").run(spec, "fd-dynamic")
tol = rs32.extras["tolerance"]
assert tol["ok"], tol
print("SHARDED_SWEEP_OK")
""")
    assert "SHARDED_SWEEP_OK" in out


def test_device_engine_precision_modes(devices8):
    out = devices8("""
import jax, numpy as np
import jax.numpy as jnp
from repro.engine import DeviceEngine, QuerySpec
from repro.jaxcompat import make_mesh

mesh = make_mesh((8,), ("model",))
scores = jax.random.normal(jax.random.PRNGKey(0), (1024,))
spec = QuerySpec(k=10)
res = DeviceEngine(mesh).run(spec, "fd-dynamic", scores=scores)
assert res.precision == "f32"             # caller dtype, honestly reported
rb = DeviceEngine(mesh, precision="bf16").run(spec, "fd-dynamic",
                                              scores=scores)
# the collectives' local top-k computes in f32 (repro.kernels.topk),
# so the bf16 mode quantizes inputs; the requested mode is recorded
assert rb.precision == "bf16" and rb.values.dtype == jnp.float32
# bf16 engine == casting the scores by hand
rc = DeviceEngine(mesh).run(spec, "fd-dynamic",
                            scores=scores.astype(jnp.bfloat16))
np.testing.assert_array_equal(np.asarray(rb.values, np.float32),
                              np.asarray(rc.values, np.float32))
try:
    DeviceEngine(mesh, precision="f8")
    raise SystemExit("bad precision must raise")
except ValueError:
    pass
print("DEVICE_PRECISION_OK")
""")
    assert "DEVICE_PRECISION_OK" in out
