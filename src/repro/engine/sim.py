"""SimEngine — the unified engine over the vectorized numpy simulator.

``prepare(topology)`` compiles a :class:`~repro.engine.plan.NetworkPlan`
once; every subsequent ``run(spec, policy)`` reuses the cached CSR,
directed edges, per-origin BFS trees / forward masks, and auto-TTLs, so
repeated queries on the same overlay skip all graph preprocessing.

Exactness contract (inherited from the PR-1 batch engine and enforced
by tests/test_engine.py + tests/test_multi_query.py):

  * a shared-stream batch of ONE reproduces ``run_query_reference``
    bit-for-bit;
  * ``rng="independent"`` (or explicit ``seeds``) reproduces
    ``run_query_reference(seed + q * n_trials + t)`` bit-for-bit for
    EVERY entry, for every registered policy.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import List, Optional, Sequence, Union

import numpy as np
from jax.profiler import TraceAnnotation

from repro.engine.api import (PRECISIONS, Engine, Policy, QuerySpec,
                              TopKResult, get_policy)
from repro.engine.plan import NetworkPlan
from repro.engine.precision import check_tolerance
from repro.p2psim.graph import Topology
from repro.p2psim.overlay import Overlay
from repro.p2psim.metrics import QUERY_BYTES, BatchMetrics, QueryMetrics
from repro.p2psim.simulate import (SimParams, _latency_mode,
                                   _run_entries, run_query_reference)

_BM_FIELDS = ("m_bw", "m_rt", "b_bw", "b_rt", "response_time_s", "accuracy")
_ALL_BM_FIELDS = ("n_reached", "n_edges_pq", "avg_degree", "m_fw",
                  "b_fw") + _BM_FIELDS


def _batch_of_one(met: QueryMetrics) -> BatchMetrics:
    """Wrap one scalar QueryMetrics as a (1, 1) BatchMetrics."""
    bm = BatchMetrics.empty(met.algorithm, 1, 1)
    for f in _ALL_BM_FIELDS:
        getattr(bm, f)[0, 0] = getattr(met, f)
    return bm


def _slice_rows(bm: BatchMetrics, lo: int, n_queries: int,
                n_trials: int) -> BatchMetrics:
    """Reshape rows [lo, lo + Q*T) of a flat (N, 1) batch to (Q, T)."""
    out = BatchMetrics.empty(bm.algorithm, n_queries, n_trials)
    hi = lo + n_queries * n_trials
    for f in _ALL_BM_FIELDS:
        getattr(out, f)[:] = getattr(bm, f)[lo:hi, 0].reshape(
            n_queries, n_trials)
    return out


class SimEngine(Engine):
    """Unified Top-k engine backend over the overlay simulator.

    ``backend`` selects the sweep implementation:

      * ``"numpy"`` (default) — the vectorized numpy batch engine;
      * ``"jax"`` — jitted XLA sweeps over the plan's depth-bucketed
        slices and static merge-fold schedule
        (``repro.engine.sim_jax``), routing the bottom-up k-list merge
        through the Pallas bitonic kernel on TPU (f32 / bf16).
        Bit-for-bit equal to the numpy backend in every RNG mode
        (the stochastic inputs are the same numpy draws), INCLUDING
        churn: finite ``lifetime_mean_s`` runs in the same jitted
        sweep via validity masks and the plan's static reroute tables
        — no numpy fallback.  The only policy that still executes on
        the numpy reference path is the two-round ``fd-stats``
        heuristic; that fallback is recorded on
        ``TopKResult.backend_used`` and warned about once per engine.

    ``use_pallas`` (jax backend only): None = the fused jnp path on
    every platform; True runs the Pallas kernels — compiled on TPU for
    f32 / bf16, interpreted off-TPU, and refused for f64 on TPU (no f64
    in Mosaic).

    ``precision`` (jax backend only): ``"f64"`` (default — the
    bit-exactness contract vs the scalar reference), ``"f32"`` or
    ``"bf16"`` (the sweeps run end-to-end in reduced precision; the
    result carries the TOLERANCE contract instead — see
    :mod:`repro.engine.precision`).  A spec's ``precision`` field
    overrides the engine default per request.  With
    ``validate_precision=True`` (default) every reduced-precision
    execution also runs the f64 sweep and records the measured
    contract (top-k recall + score rtol) in
    ``TopKResult.extras["tolerance"]``; benchmarks switch it off to
    time the reduced sweep alone.

    ``shard`` (jax backend only): run the forward/merge sweep through
    ``shard_map`` over all local devices on the batch-entry axis —
    each device holds only its slice of the per-entry working set
    (how million-peer plans fit in device memory).
    """

    backend = "sim"

    def __init__(self, top: Optional[Union[Topology, NetworkPlan]] = None,
                 params: Optional[SimParams] = None, *,
                 backend: str = "numpy",
                 use_pallas: Optional[bool] = None,
                 precision: str = "f64",
                 validate_precision: bool = True,
                 shard: bool = False):
        """Build the engine (and compile ``top``'s plan when given)."""
        if backend not in ("numpy", "jax"):
            raise ValueError("backend must be 'numpy' or 'jax', "
                             f"got {backend!r}")
        if precision not in PRECISIONS:
            raise ValueError(
                f"precision must be one of {PRECISIONS}, got {precision!r}")
        if backend != "jax" and precision != "f64":
            raise ValueError(
                "reduced precision requires backend='jax' — the numpy "
                "reference sweep is the f64 ground truth")
        self.params = params if params is not None else SimParams()
        self.plan: Optional[NetworkPlan] = None
        self.backend = "sim" if backend == "numpy" else "sim-jax"
        self._backend = backend
        self._use_pallas = use_pallas
        self._precision = precision
        self._validate_precision = validate_precision
        self._shard = shard
        self._warned_fallback = False
        if top is not None:
            self.prepare(top)

    def _fallback(self, reason: str) -> str:
        """Record a numpy-path fallback; warn AT MOST ONCE per engine."""
        if self._backend == "jax" and not self._warned_fallback:
            self._warned_fallback = True
            warnings.warn(
                f"SimEngine(backend='jax'): {reason}; running on the "
                "numpy reference path (reported on "
                "TopKResult.backend_used)", RuntimeWarning, stacklevel=4)
        return "sim"

    def prepare(self, top: Union[Topology, Overlay, NetworkPlan]
                ) -> NetworkPlan:
        """Compile (or adopt) the overlay's NetworkPlan.

        Passing a live :class:`~repro.p2psim.overlay.Overlay` binds the
        plan to it: every subsequent ``run`` / ``run_many`` re-resolves
        the plan against the overlay's current version
        (:meth:`NetworkPlan.sync` — incremental, not a recompile), so
        the engine keeps serving while the network churns."""
        self.plan = top if isinstance(top, NetworkPlan) else NetworkPlan(top)
        return self.plan

    def run(self, spec: Optional[QuerySpec] = None,
            policy: Union[str, Policy] = "fd-dynamic", *,
            params: Optional[SimParams] = None) -> TopKResult:
        """Execute ``spec`` under ``policy`` on the prepared overlay.

        This is the batch-of-1 case of :meth:`run_many`.
        """
        spec = spec if spec is not None else QuerySpec()
        return self.run_many([spec], [policy], params=params)[0]

    # ---- dynamic batching (run_many) -------------------------------------

    def _effective(self, spec: QuerySpec,
                   params: Optional[SimParams]) -> SimParams:
        """The ``SimParams`` this spec executes under (spec overrides
        applied)."""
        p = params if params is not None else self.params
        if spec.k is not None:
            p = dataclasses.replace(p, k=spec.k)
        if spec.seed is not None:
            p = dataclasses.replace(p, seed=spec.seed)
        if spec.latency_model is not None:
            p = dataclasses.replace(p, latency_model=spec.latency_model)
        return p

    @staticmethod
    def _coalescable(spec: QuerySpec, pol: Policy) -> bool:
        """True when the spec's entries can be fused with other specs'
        onto one sweep without changing a single drawn bit.

        Independent-stream entries (``rng="independent"`` or explicit
        ``seeds``) draw from their own generators, so their results
        depend only on (origin, entry seed, params, policy) — fusing is
        free.  A SHARED-stream spec draws batch-shaped arrays from one
        generator, so its draws depend on the whole batch shape — except
        for a batch of ONE, which is bit-for-bit the scalar reference on
        its seed, i.e. exactly the independent entry with that seed.
        Multi-entry shared specs therefore execute alone; the two-round
        ``fd-stats`` heuristic always does.
        """
        if pol.algorithm == "fd-stats":
            return False
        return spec.independent or (len(spec.origins) * spec.n_trials == 1)

    def _entry_seeds(self, spec: QuerySpec, p: SimParams) -> np.ndarray:
        """Per-entry RNG seeds, flattened — explicit ``seeds`` verbatim,
        else the engine's ``seed + q * n_trials + t`` derivation."""
        Q, T = len(spec.origins), spec.n_trials
        if spec.seeds is not None:
            seeds = np.asarray(spec.seeds, dtype=np.int64)
            if seeds.shape != (Q, T):
                raise ValueError(
                    f"seeds must be ({Q}, {T}), got {seeds.shape}")
            return seeds.reshape(-1)
        return p.seed + np.arange(Q * T, dtype=np.int64)

    def run_many(self, specs: Sequence[QuerySpec],
                 policies: Union[str, Policy,
                                 Sequence[Union[str, Policy]]]
                 = "fd-dynamic", *,
                 params: Optional[SimParams] = None) -> List[TopKResult]:
        """Execute a request batch, coalescing compatible specs.

        Specs sharing an execution signature — same resolved ``Policy``
        and same effective ``(k, latency_model)`` — whose entries are
        independently seeded (see :meth:`_coalescable`) are fused onto
        ONE batched sweep: their (origin, seed) entries concatenate into
        a single flattened spec with explicit per-entry seeds, reusing
        the plan's cached statics / ``DepthSlices`` and (on the jax
        backend) one jit trace for the whole group.  Every returned
        result is entry-wise bit-exact with a sequential ``run`` of its
        spec; ``TopKResult.batch_size`` records how many requests shared
        the executed sweep.
        """
        with TraceAnnotation("fd.engine.run_many",
                             requests=len(specs)) as span:
            pols = self._zip_policies(specs, policies)
            results: List[Optional[TopKResult]] = [None] * len(specs)
            groups: dict = {}               # signature -> [request index]
            for i, (spec, pol) in enumerate(zip(specs, pols)):
                p = self._effective(spec, params)
                if not self._coalescable(spec, pol):
                    results[i] = self._execute(spec, pol, p)
                    continue
                prec = spec.precision or self._precision
                groups.setdefault((pol, p.k, p.latency_model, prec),
                                  []).append(i)
            span.set_metadata(groups=len(groups) + sum(
                r is not None for r in results))
            for (pol, k, lm, prec), idxs in groups.items():
                if len(idxs) == 1:          # nothing to fuse: direct path
                    i = idxs[0]
                    results[i] = self._execute(
                        specs[i], pol, self._effective(specs[i], params))
                    continue
                origins, seeds, shapes = [], [], []
                for i in idxs:
                    spec = specs[i]
                    p = self._effective(spec, params)
                    origins.append(np.repeat(
                        np.asarray(spec.origins, np.int64), spec.n_trials))
                    seeds.append(self._entry_seeds(spec, p))
                    shapes.append((len(spec.origins), spec.n_trials))
                fused = QuerySpec(
                    origins=tuple(int(o) for o in np.concatenate(origins)),
                    n_trials=1, k=k, latency_model=lm, precision=prec,
                    seeds=np.concatenate(seeds)[:, None])
                res = self._execute(fused, pol,
                                    self._effective(fused, params))
                lo = 0
                for i, (Q, T) in zip(idxs, shapes):
                    hi = lo + Q * T
                    results[i] = dataclasses.replace(
                        res, metrics=_slice_rows(res.metrics, lo, Q, T),
                        values=(None if res.values is None else
                                res.values.reshape(-1, k)[lo:hi]
                                .reshape(Q, T, k)),
                        indices=(None if res.indices is None else
                                 res.indices.reshape(-1, k)[lo:hi]
                                 .reshape(Q, T, k)),
                        batch_size=len(idxs), extras=dict(res.extras))
                    lo += Q * T
            return results

    def _execute(self, spec: QuerySpec, pol: Policy,
                 p: SimParams) -> TopKResult:
        """Run one (already resolved) spec on the prepared overlay."""
        if self.plan is None:
            raise RuntimeError("call SimEngine.prepare(topology) first")
        prec = spec.precision or self._precision
        if pol.algorithm == "fd-stats":
            if prec != "f64":
                raise ValueError(
                    "fd-stats runs on the scalar reference path, which "
                    "is f64-only; request precision='f64' (or None)")
            self._sync(p)
            return self._run_stats(spec, pol, p)
        if prec != "f64" and self._backend != "jax":
            raise ValueError(
                f"spec requests precision={prec!r} but the numpy backend "
                "only runs f64 (it IS the ground truth); use "
                "SimEngine(backend='jax')")

        origins = np.atleast_1d(np.asarray(spec.origins, dtype=np.int64))
        Q, T = len(origins), spec.n_trials
        ent_seeds = self._entry_seeds(spec, p)
        fw_strategy = ("basic" if pol.algorithm in ("cn", "cn_star")
                       else pol.strategy)
        with TraceAnnotation("fd.engine.statics") as span:
            self._sync(p)
            n_statics = len(self.plan._statics)
            t0 = time.perf_counter()
            sts, st_of_q = self.plan.origin_statics(origins, p.ttl,
                                                    fw_strategy)
            # statics wall counts as compile only when this call
            # actually BUILT something — a warm plan reports 0.0, so
            # serving-layer assertions on "no compile on the steady
            # path" hold
            built = len(self.plan._statics) - n_statics
            compile_s = time.perf_counter() - t0 if built else 0.0
            # replica placement is retrieval-phase only (FD paths); the
            # CN baselines never enter the owner-fetch fallback
            rep = (None if pol.algorithm in ("cn", "cn_star")
                   else self.plan.replica_table(p))
            span.set_metadata(built=built)
        ent_st = np.repeat(st_of_q, T)
        ent_origin = np.repeat(origins, T)
        extras: dict = {}
        t0 = time.perf_counter()
        if self._backend == "jax":
            from repro.engine.sim_jax import run_entries_jax
            res = run_entries_jax(self.plan, sts, ent_st, ent_origin,
                                  ent_seeds, self.plan.top.n, p,
                                  pol.algorithm, pol.dynamic,
                                  pol.lifetime_mean_s, spec.independent,
                                  use_pallas=self._use_pallas,
                                  replicas=rep, precision=prec,
                                  shard=self._shard)
            used = "sim-jax"
        else:
            res = _run_entries(sts, ent_st, ent_origin, ent_seeds,
                               self.plan.top.n, p, pol.algorithm,
                               pol.dynamic, pol.lifetime_mean_s,
                               spec.independent, replicas=rep)
            used = "sim"
        run_s = time.perf_counter() - t0
        compile_s += res.pop("jax_compile_s", 0.0)
        traces = res.pop("jax_traces", 0)
        if traces:
            extras["jax_traces"] = traces
        vals = res.pop("values", None)
        owns = res.pop("owners", None)
        if prec != "f64" and self._validate_precision:
            # the tolerance contract: rerun the SAME entries in f64 and
            # measure recall / rtol of the reduced result against it
            # (the f64 rerun takes the platform's f64 path: f64 never
            # enters a compiled kernel, and both paths give the same bits)
            res64 = run_entries_jax(self.plan, sts, ent_st, ent_origin,
                                    ent_seeds, self.plan.top.n, p,
                                    pol.algorithm, pol.dynamic,
                                    pol.lifetime_mean_s, spec.independent,
                                    use_pallas=None,
                                    replicas=rep, precision="f64",
                                    shard=self._shard)
            report = check_tolerance(prec, vals, owns,
                                     res64["values"], res64["owners"])
            extras["tolerance"] = report.summary()

        bm = BatchMetrics.empty(pol.algorithm, Q, T)
        n_reached_s = np.array([len(st.idx) for st in sts], np.int64)
        n_edges_s = np.array([st.n_edges_pq for st in sts], np.int64)
        avg_deg_s = np.array([st.avg_degree for st in sts])
        bm.n_reached[:] = n_reached_s[st_of_q, None]
        bm.n_edges_pq[:] = n_edges_s[st_of_q, None]
        bm.avg_degree[:] = avg_deg_s[st_of_q, None]
        bm.m_fw[:] = res["m_fw"].reshape(Q, T)
        bm.b_fw[:] = res["m_fw"].reshape(Q, T) * QUERY_BYTES
        for f in _BM_FIELDS:
            getattr(bm, f)[:] = res[f].reshape(Q, T)
        return TopKResult(policy=pol.name, backend=self.backend, k=p.k,
                          backend_used=used, topology=self.plan.top.kind,
                          latency_model=p.latency_model, metrics=bm,
                          precision=prec,
                          values=(None if vals is None
                                  else vals.reshape(Q, T, p.k)),
                          indices=(None if owns is None
                                   else owns.reshape(Q, T, p.k)),
                          compile_s=compile_s, run_s=run_s,
                          extras=extras)

    def _sync(self, p: SimParams) -> None:
        """Catch a live overlay's plan up by version; validate the
        latency model's name and coordinates."""
        if self.plan.overlay is not None:
            self.plan.sync()
        _latency_mode(self.plan.top, p)

    # ---- statistics heuristic (paper §3.3 + Fig 7) ----------------------

    def _run_stats(self, spec: QuerySpec, pol: Policy,
                   p: SimParams) -> TopKResult:
        """Two-round protocol: round 1 full FD gathers per-child best-rank
        stats; round 2 forwards Q only to children whose best past score
        ranked above ``z * k`` in the parent's merged list."""
        used = self._fallback("the two-round fd-stats heuristic has no "
                              "jitted lowering")
        t_start = time.perf_counter()
        origins = np.atleast_1d(np.asarray(spec.origins, dtype=np.int64))
        if len(origins) != 1 or spec.n_trials != 1:
            raise ValueError("fd-stats runs one origin x one trial per call")
        if spec.seeds is not None:
            seeds = np.asarray(spec.seeds, dtype=np.int64)
            if seeds.shape != (1, 1):
                raise ValueError(f"seeds must be (1, 1), got {seeds.shape}")
            p = dataclasses.replace(p, seed=int(seeds[0, 0]))
        origin = int(origins[0])
        top = self.plan.top
        if p.ttl == 0:
            # resolve auto-TTL once from the plan cache and thread it
            # through both rounds (round 2 prunes AFTER TTL resolution,
            # so the full-topology eccentricity is the right value twice)
            p = dataclasses.replace(p, ttl=self.plan.auto_ttl(origin))
        met1, st = run_query_reference(top, origin, p, return_state=True)
        children = st["children"]
        ms = st["merged_scores"]
        n = top.n
        keep = np.ones(n, bool)
        k = p.k
        for v in range(n):
            for c in children[v]:
                if ms[v] is None or ms[c] is None:
                    continue
                # best rank of c's subtree contribution within v's merge
                in_c = np.isin(ms[v], ms[c])
                ranks = np.flatnonzero(in_c)
                best = ranks[0] if len(ranks) else k
                if best >= pol.z * k:
                    keep[c] = False
        met2, st2 = run_query_reference(top, origin, p, child_mask=keep,
                                        return_state=True)
        # accuracy of round 2 vs round-1 TRUTH (the full reach set) —
        # pruning shrinks P_Q, so met2.accuracy alone would be trivially 1
        reached1 = st["reached"]
        idx1 = np.flatnonzero(reached1)
        true_scores = st["scores"][idx1].reshape(-1)
        top_true = np.sort(true_scores)[::-1][:k]
        got = st2["merged_scores"][origin]
        acc = float(np.intersect1d(top_true, got).size) / k \
            if got is not None else 0.0
        reduction = 1.0 - met2.total_bytes / max(met1.total_bytes, 1)
        return TopKResult(
            policy=pol.name, backend=self.backend, k=k,
            backend_used=used, topology=top.kind,
            latency_model=p.latency_model, metrics=_batch_of_one(met2),
            run_s=time.perf_counter() - t_start,
            extras={"metrics_full": met1, "metrics_pruned": met2,
                    "comm_reduction": reduction, "accuracy": acc,
                    "z": pol.z})
