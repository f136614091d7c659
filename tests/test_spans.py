"""Spans and counters inside the served path, read back from a trace.

Serves a fixed backlog through ``QueryServer`` over a warm
``SimEngine(backend="jax")`` under ``jax.profiler.trace`` and reads the
program's ``fd.*`` host spans, with their stats, out of the trace's
xplane:

  * every span of the served path appears, each engine span nests
    inside ``fd.engine.run_many``, which nests inside
    ``fd.server.dispatch``;
  * the stats count what they say: ``rows`` is the entry group's
    power-of-two bucket, ``d2h_bytes`` the bytes of the sweep outputs
    copied back (worked out here from the tree's shape), ``traced`` is
    0 on a warm engine, ``built`` is 0 on a warm plan;
  * warmed on queries that send no urgent lists, a dispatch whose
    queries do fetches them without compiling;
  * each result's ``extras["dispatch"]`` names its dispatch span;
  * answers are bit-identical with the profiler on and off.
"""
import dataclasses
import glob
import os
from typing import Dict, List

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.engine import (QueryServer, QuerySpec, ServerConfig, SimEngine,
                          get_policy)
from repro.engine import sim_jax
from repro.engine.sim_jax import URGENT_CHUNK
from repro.p2psim import SimParams, barabasi_albert

TOP = barabasi_albert(96, m=2, seed=3)
PA = SimParams(seed=11)
# served before start, max_batch 4: dispatch 1 takes the first four
# (origin 0 three times -> bucket 4, origin 17 once -> bucket 1),
# dispatch 2 the last two (one entry per origin -> bucket 1)
ORIGINS = (0, 0, 17, 0, 17, 0)
DISPATCH_OF = (1, 1, 1, 1, 2, 2)
BUCKETS = (1, 2, 4)

SERVER_SPANS = ("fd.server.linger", "fd.server.dispatch")
ENGINE_SPANS = ("fd.engine.run_many", "fd.engine.statics",
                "fd.engine.draws", "fd.engine.stage", "fd.engine.sweep",
                "fd.engine.copy_back", "fd.engine.epilogue")
FD_ONLY_SPANS = ("fd.engine.truth", "fd.engine.retrieval")

POLICIES = {
    "fd-dynamic": get_policy("fd-dynamic"),
    "fd-dynamic-churn": get_policy("fd-dynamic").variant(
        lifetime_mean_s=30.0),
    "cn": get_policy("cn"),
}

_FIELDS = ("m_fw", "m_bw", "m_rt", "b_fw", "b_bw", "b_rt",
           "response_time_s", "accuracy")


@dataclasses.dataclass
class Span:
    name: str
    line: int             # index of the host thread's line
    start: int
    end: int
    stats: Dict[str, int]

    def within(self, other: "Span") -> bool:
        return (self.line == other.line and other.start <= self.start
                and self.end <= other.end)


def _specs() -> List[QuerySpec]:
    return [QuerySpec(origins=(o,), seeds=[[500 + i]])
            for i, o in enumerate(ORIGINS)]


def _serve(engine, policy) -> list:
    server = QueryServer(engine, ServerConfig(max_batch=4,
                                              batch_window_s=0.05))
    handles = [server.submit(s, policy) for s in _specs()]
    server.start()
    results = [h.result(timeout=300) for h in handles]
    server.stop()
    return results


def _read_spans(log_dir: str) -> List[Span]:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1, paths
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("fd."):
                    out.append(Span(ev.name, li, int(ev.start_ns),
                                    int(ev.start_ns + ev.duration_ns),
                                    {k: int(v) for k, v in ev.stats}))
    return out


@pytest.fixture(scope="module", params=sorted(POLICIES))
def served(request, tmp_path_factory):
    """One policy's backlog served twice on a warm engine, untraced and
    traced, with the traced run's spans."""
    policy = POLICIES[request.param]
    engine = SimEngine(TOP, PA, backend="jax")
    server = QueryServer(engine)
    for o in sorted(set(ORIGINS)):
        server.warm(QuerySpec(origins=(o,), seeds=[[1]]), policy,
                    batch_sizes=BUCKETS)
    plain = _serve(engine, policy)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        traced = _serve(engine, policy)
    return {"policy": policy, "engine": engine, "plain": plain,
            "traced": traced, "spans": _read_spans(log_dir)}


def _named(spans, name) -> List[Span]:
    return [s for s in spans if s.name == name]


def test_every_span_appears(served):
    names = {s.name for s in served["spans"]}
    want = set(SERVER_SPANS + ENGINE_SPANS)
    if served["policy"].algorithm == "fd":
        want |= set(FD_ONLY_SPANS)
    assert want <= names, sorted(want - names)
    assert names <= want, sorted(names - want)


def test_engine_spans_nest_in_run_many_in_dispatch(served):
    spans = served["spans"]
    dispatches = _named(spans, "fd.server.dispatch")
    run_manys = _named(spans, "fd.engine.run_many")
    assert len(dispatches) == len(run_manys) == 2
    for rm in run_manys:
        assert sum(rm.within(d) for d in dispatches) == 1
    for s in spans:
        if s.name.startswith("fd.engine.") and s.name != \
                "fd.engine.run_many":
            assert sum(s.within(rm) for rm in run_manys) == 1, s
    epilogues = _named(spans, "fd.engine.epilogue")
    for s in _named(spans, "fd.engine.truth") + _named(
            spans, "fd.engine.retrieval"):
        assert sum(s.within(e) for e in epilogues) == 1, s
    # the linger precedes its dispatch on the dispatcher's own line
    for ling in _named(spans, "fd.server.linger"):
        assert ling.line == dispatches[0].line


def _reached(engine, origin, policy):
    strategy = "basic" if policy.algorithm == "cn" else policy.strategy
    (st,), _ = engine.plan.origin_statics(np.array([origin]), PA.ttl,
                                          strategy)
    reach = len(st.idx)
    levels = int(st.depth[st.idx].max()) + 1
    return reach, levels


def test_stats_count_what_they_say(served):
    spans, policy = served["spans"], served["policy"]
    k = PA.k
    assert [s.stats["requests"] for s in _named(
        spans, "fd.server.linger")] == [4, 2]
    assert [s.stats["requests"] for s in _named(
        spans, "fd.server.dispatch")] == [4, 2]
    assert [(s.stats["requests"], s.stats["groups"]) for s in _named(
        spans, "fd.engine.run_many")] == [(4, 1), (2, 1)]
    assert [s.stats["built"] for s in _named(
        spans, "fd.engine.statics")] == [0, 0]
    assert [s.stats["entries"] for s in _named(
        spans, "fd.engine.draws")] == [4, 2]
    stages = _named(spans, "fd.engine.stage")
    assert sorted((s.stats["entries"], s.stats["rows"]) for s in stages) \
        == [(1, 1), (1, 1), (1, 1), (3, 4)]
    sweeps = _named(spans, "fd.engine.sweep")
    assert sorted(s.stats["rows"] for s in sweeps) == [1, 1, 1, 4]
    assert all(s.stats["traced"] == 0 for s in sweeps)
    assert all(s.stats["h2d_bytes"] > 0 for s in sweeps)
    if policy.algorithm == "fd":
        for name in FD_ONLY_SPANS:
            assert [s.stats["entries"] for s in _named(spans, name)] \
                == [4, 2]

    # copies back: the packed rows of the padded group and the origin's
    # list, so the bytes follow from the tree's shape (f64 send and
    # arrival times over the reached peers, the origin sending no list;
    # bool liveness under churn; the origin's f64 scores and int32
    # owners; int64 Strategy-1 skip counts), plus the gathered lists of
    # accepted urgent children in whole chunks of URGENT_CHUNK rows
    churn = policy.lifetime_mean_s != float("inf")
    st1 = policy.algorithm == "fd" and policy.strategy != "basic"
    for stage, sweep in zip(sorted(stages, key=lambda s: s.start),
                            sorted(sweeps, key=lambda s: s.start)):
        assert stage.end <= sweep.start
    copies = sorted(_named(spans, "fd.engine.copy_back"),
                    key=lambda s: s.start)
    assert len(copies) == 4
    # dispatch 1 runs origin 0 (bucket 4) then 17 (bucket 1); dispatch
    # 2 runs origin 0 then 17, one row each
    order = [(0, 4), (17, 1), (0, 1), (17, 1)]
    for cb, (origin, rows) in zip(copies, order):
        reach, levels = _reached(served["engine"], origin, policy)
        urgent = 0
        if policy.algorithm == "cn":
            per_row = reach * 8
            transfers = levels
        else:
            per_row = (reach * 8 + (reach - 1) * 8 + (reach if churn else 0)
                       + k * (8 + 4) + (8 if st1 else 0))
            transfers = 4 + churn + st1
            assert cb.stats["urgent_rows"] >= 0
            chunks = -(-cb.stats["urgent_rows"] // URGENT_CHUNK)
            urgent = chunks * URGENT_CHUNK * k * (8 + 4)
            transfers += 2 * chunks
        assert cb.stats["d2h_bytes"] == rows * per_row + urgent, \
            (origin, rows)
        assert cb.stats["transfers"] == transfers


def test_results_name_their_dispatch(served):
    numbers = {s.stats["dispatch"] for s in _named(
        served["spans"], "fd.server.dispatch")}
    assert numbers == {1, 2}
    got = tuple(r.extras["dispatch"] for r in served["traced"])
    assert got == DISPATCH_OF
    assert tuple(r.extras["dispatch"] for r in served["plain"]) == \
        DISPATCH_OF


def test_answers_identical_with_profiler_on_and_off(served):
    for i, (a, b) in enumerate(zip(served["plain"], served["traced"])):
        for f in _FIELDS:
            np.testing.assert_array_equal(getattr(a.metrics, f),
                                          getattr(b.metrics, f),
                                          err_msg=f"request {i}: {f}")
        for f in ("values", "indices"):
            x, y = getattr(a, f), getattr(b, f)
            assert (x is None) == (y is None)
            if x is not None:
                np.testing.assert_array_equal(x, y)


def test_numpy_backend_records_server_and_engine_spans(tmp_path):
    """The numpy backend has no device phases: its dispatches record the
    server's spans, ``run_many`` and the statics, and nothing else."""
    engine = SimEngine(TOP, PA)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        results = _serve(engine, "fd-dynamic")
    spans = _read_spans(str(tmp_path))
    assert {s.name for s in spans} == set(SERVER_SPANS) | {
        "fd.engine.run_many", "fd.engine.statics"}
    assert tuple(r.extras["dispatch"] for r in results) == DISPATCH_OF
    built = [s.stats["built"] for s in _named(spans, "fd.engine.statics")]
    assert built == [2, 0]          # the cold plan builds both origins


# link latencies and wait budgets under which late children are common
# and many of their urgent lists reach the origin in time
URGENT_PA = SimParams(seed=11, latency_mean_s=0.2, latency_var=0.3 ** 2,
                      t_qsnd_s=0.2, t_slsnd_s=0.3)
QUIET_SEEDS = {0: 8, 17: 2}          # per origin: no urgent list accepted
URGENT_SEEDS = ((0, 19), (0, 22), (17, 12), (17, 10))


@pytest.mark.parametrize("name", ["fd-dynamic", "fd-dynamic-churn"])
def test_warm_gather_serves_urgent_rows_without_compiling(name, tmp_path,
                                                         monkeypatch):
    """``QueryServer.warm`` on queries that send no urgent lists still
    compiles the urgent-row gather, so a served dispatch whose queries
    do fetch urgent rows traces nothing: every sweep span reads
    ``traced`` 0 and no result reports ``jax_traces``."""
    policy = POLICIES[name]
    rows = []
    accept = sim_jax._accept_urgent_origin

    def spy(org_v, org_o, ue, cv, co, k):
        rows.append(len(ue))
        accept(org_v, org_o, ue, cv, co, k)
    monkeypatch.setattr(sim_jax, "_accept_urgent_origin", spy)
    engine = SimEngine(TOP, URGENT_PA, backend="jax")
    warm = QueryServer(engine)
    for o, seed in QUIET_SEEDS.items():
        warm.warm(QuerySpec(origins=(o,), seeds=[[seed]]), policy,
                  batch_sizes=BUCKETS)
    assert rows and not any(rows), rows
    rows.clear()
    server = QueryServer(engine, ServerConfig(max_batch=4,
                                              batch_window_s=0.05))
    handles = [server.submit(QuerySpec(origins=(o,), seeds=[[seed]]),
                             policy) for o, seed in URGENT_SEEDS]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        server.start()
        results = [h.result(timeout=300) for h in handles]
        server.stop()
    spans = _read_spans(str(tmp_path))
    sweeps = _named(spans, "fd.engine.sweep")
    copies = _named(spans, "fd.engine.copy_back")
    assert len(sweeps) == len(copies) == 2          # one dispatch, 2 origins
    assert all(s.stats["traced"] == 0 for s in sweeps)
    assert all(r.extras.get("jax_traces", 0) == 0 for r in results)
    assert all(r.compile_s == 0 for r in results)
    assert sum(rows) > 0
    assert sum(c.stats["urgent_rows"] for c in copies) == sum(rows)
