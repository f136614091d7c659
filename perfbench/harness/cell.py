"""One run of one cell: set up, warm, measure, check, report.

The served path under test is the program's own: ``QueryServer`` over
``SimEngine(backend="jax")`` (``run_many`` -> ``run_entries_jax``: host
draws, the jitted device sweep, copy back, the numpy epilogue).  The
benchmark hands it an overlay it built itself and single-entry
``QuerySpec`` requests whose origins and seeds come from ``--seed``,
and takes back the answers, ``TopKResult.queue_s`` and the server's
counters.  Its own spans go around the server's calls into the engine.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import List, Optional

from harness import bench, check, overlay, traffic
from harness import trace as tracing

SWEEP_PROGRAM = "_fd_sweep_impl"      # the jitted sweep's module name
WARM_SEED = 20050101                  # seeds of the warm-up queries


@dataclasses.dataclass
class Run:
    """What a finished run hands to the metric readers."""

    config: dict
    traffic: dict
    device_kind: str
    setup_s: float
    requests: List[traffic.Request]
    engine_calls: List[tuple]     # (start, end) of each run_many call
    batches: List[List[int]]      # request seeds of each run_many call
    trace: Optional[dict] = None

    @property
    def answered(self) -> List[traffic.Request]:
        return [r for r in self.requests if math.isfinite(r.latency_s)]


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; exit, printing no result, unless
    they are at least ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SystemExit(f"perfbench: no TPU (JAX reports "
                         f"{info['platform']!r}); nothing was run")
    if info["count"] < chips:
        raise SystemExit(f"perfbench: the cell needs {chips} TPU chips, "
                         f"JAX reports {info['count']}")
    return info


class CompileCounter:
    """Counts JAX traces and compilations (compiled or loaded from the
    persistent cache) while ``active``."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.active = False
        self.counts = {"traces": 0, "compiles": 0}
        self.lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if not self.active:
            return
        key = ("traces" if event == self.TRACE else
               "compiles" if event == self.COMPILE else None)
        if key:
            with self.lock:
                self.counts[key] += 1


class GcPauses:
    """Python's cyclic collections, by generation, from now to
    ``stop()``: how many and how long."""

    def __init__(self):
        self.n = [0, 0, 0]
        self.s = [0.0, 0.0, 0.0]
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            g = int(info["generation"])
            self.n[g] += 1
            self.s[g] += time.perf_counter() - self._t0
            self._t0 = None

    def stop(self) -> None:
        gc.callbacks.remove(self._on)

    def __str__(self) -> str:
        return ", ".join(f"gen{g} {self.n[g]} in {self.s[g]:.3f} s"
                         for g in range(3))


def _buckets(max_batch: int) -> List[int]:
    """The entry buckets a sweep of 1..max_batch entries can take (the
    engine pads each origin's group to a power of two)."""
    out, b = [], 1
    while True:
        out.append(b)
        if b >= max_batch:
            return out
        b *= 2


def _policy(pol: dict):
    from repro.engine import get_policy
    return get_policy(pol["name"]).variant(
        lifetime_mean_s=check.lifetime(pol))


class Session:
    """A cell's system under test, set up and warm: the benchmark's
    overlay, ``SimEngine(backend="jax")`` behind a ``QueryServer``, and
    every (origin, entry bucket) program the traffic can use compiled.

    ``info`` is the device as ``device_info`` found it (None: read it
    here without the TPU check, as the CPU tests do).  ``precision``
    overrides the configuration's (the lower-precision control).
    ``config_override`` and ``traffic_override`` replace keys of the
    configuration and the traffic (the CPU tests shrink the overlay and
    check every answer with them).
    """

    def __init__(self, workload: str, *, t_start: float,
                 precision: Optional[str] = None,
                 info: Optional[dict] = None, root: str = bench.CHECKOUT,
                 config_override: Optional[dict] = None,
                 traffic_override: Optional[dict] = None):
        import jax
        from repro.engine import (QueryServer, QuerySpec, ServerConfig,
                                  SimEngine)
        from repro.p2psim.graph import Topology
        from repro.p2psim.simulate import SimParams

        self.spec = bench.cell(bench.load(root), workload, root)
        self.cfg = cfg = dict(self.spec["config"], **(config_override or {}))
        self.traffic = tr = dict(self.spec["traffic"],
                                 **(traffic_override or {}))
        if info is None:
            d = jax.devices()
            info = {"platform": d[0].platform, "kind": d[0].device_kind,
                    "count": len(d)}
        self.info = info
        self.counter = CompileCounter()
        self.reference = check.load_reference(cfg, root)
        self.ov = ov = overlay.build(cfg["overlay"],
                                     check=config_override is None,
                                     root=root)
        top = Topology(ov.n, ov.neighbors, ov.kind, coords=ov.coords)
        prec = precision or cfg["precision"]
        self.engine = SimEngine(top, SimParams(**cfg["params"]),
                                backend="jax", precision=prec,
                                validate_precision=False)
        self.calls: List[tuple] = []
        self.batches: List[List[int]] = []
        self.t_open = math.inf
        engine_run_many = self.engine.run_many

        def run_many(specs, policies="fd-dynamic", **kw):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.engine.run_many"):
                res = engine_run_many(specs, policies, **kw)
            self.calls.append((t0 - self.t_open,
                               time.perf_counter() - self.t_open))
            self.batches.append([int(s.seeds[0][0]) for s in specs])
            return res
        self.engine.run_many = run_many

        srv = tr["server"]
        self.server = QueryServer(self.engine, ServerConfig(
            max_queue=int(srv["max_queue"]),
            max_batch=int(srv["max_batch"]),
            batch_window_s=float(srv["batch_window_s"])))
        self.policy = _policy(cfg["policy"])
        self.pool = list(cfg["overlay"]["origins"])
        buckets = _buckets(int(srv["max_batch"]))
        self.query_spec = QuerySpec
        for o in self.pool:
            self.server.warm(QuerySpec(origins=(o,), seeds=[[WARM_SEED + o]]),
                             self.policy, batch_sizes=buckets)
        self.calls.clear()
        self.batches.clear()
        self.server.start()
        self.setup_s = time.perf_counter() - t_start
        log(f"setup: {self.setup_s:.3f} s (overlay {ov.kind} n={ov.n} "
            f"edges={ov.n_edges}, {len(self.pool)} origins x buckets "
            f"{buckets} warmed, precision {prec})")

    def submit(self, r: traffic.Request):
        return self.server.submit(
            self.query_spec(origins=(r.origin,), seeds=[[r.seed]]),
            self.policy)

    def window(self, tr: dict, seed: int, seconds: float,
               trace: bool) -> Run:
        """Serve ``tr`` for ``seconds``; every request sent is waited
        for (up to the drain limit).  With ``trace`` the profiler
        records the window, and the run carries its reduction."""
        import jax
        log_dir = None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            log_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        self.calls.clear()
        self.batches.clear()
        self.counter.counts = {"traces": 0, "compiles": 0}
        self.counter.active = True
        pauses = GcPauses()
        self.t_open = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            if tr["loop"] == "open":
                reqs = traffic.open_schedule(tr, seed, seconds, self.pool)
                late = traffic.run_open(self.submit, reqs, self.t_open,
                                        seconds, float(tr["drain_s"]))
                log(f"open loop: {len(reqs)} requests due in {seconds} s, "
                    f"sender late by {late['late_mean_s']:.6f} s on "
                    f"average, {late['late_max_s']:.6f} s at most")
            elif tr["loop"] == "closed":
                stream = traffic.ClosedStream(tr, seed, self.pool)
                reqs = traffic.run_closed(self.submit, stream, self.t_open,
                                          seconds, float(tr["drain_s"]))
                log(f"closed loop: {tr['clients']} clients sent "
                    f"{len(reqs)} requests in {seconds} s")
            else:
                raise ValueError(f"unknown loop {tr['loop']!r}")
        self.counter.active = False
        t_end = time.perf_counter() - self.t_open
        pauses.stop()
        if trace:
            jax.profiler.stop_trace()
        log(f"window: {t_end:.3f} s to the last answer; inside it "
            f"{self.counter.counts['traces']} traces and "
            f"{self.counter.counts['compiles']} compilations")
        busy = sum(end - start for start, end in self.calls)
        sizes = [len(b) for b in self.batches]
        lone = sorted(end - start for (start, end), b in
                      zip(self.calls, self.batches) if len(b) == 1)
        log(f"engine: {len(sizes)} dispatches of {sum(sizes)} requests "
            f"(sizes {dict(sorted(collections.Counter(sizes).items()))}), "
            f"{busy:.3f} s inside run_many, "
            f"{busy / max(1, sum(sizes)):.4f} s per request; one-request "
            f"dispatches min/median/max "
            f"{traffic.percentile(lone, 0):.4f}/"
            f"{traffic.percentile(lone, 50):.4f}/"
            f"{traffic.percentile(lone, 100):.4f} s")
        log(f"gc in window: {pauses}")
        red = None
        if trace:
            red = tracing.reduce(tracing.load(tracing.find_xplane(log_dir)),
                                 SWEEP_PROGRAM)
            shutil.rmtree(log_dir, ignore_errors=True)
            log(f"trace: window {red['window_s']:.6f} s, device busy "
                f"{red['busy_s']:.6f} s, sweep {red['sweep_device_s']:.6f} "
                f"s over {red['sweeps']} sweeps")
        return Run(self.cfg, tr, self.info["kind"], self.setup_s, reqs,
                   list(self.calls), list(self.batches), red)

    def close(self) -> int:
        """Stop the server, free the program's state; return the peak
        device memory of the fullest chip the cell uses."""
        import jax
        self.server.stop(drain=False)
        m = self.server.metrics()
        log(f"server: served {m.served} shed {m.shed} timed out "
            f"{m.timed_out} failed {m.failed}; requests per dispatch "
            f"{m.dispatch_hist}")
        peak = 0
        for d in jax.local_devices()[:int(self.spec["cell"]["chips"])]:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        del self.server, self.engine
        gc.collect()
        return peak


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, **session) -> dict:
    """Run ``workload`` once and return the result line's object."""
    ses = Session(workload, t_start=t_start, **session)
    run = ses.window(ses.traffic, seed, seconds, trace)
    peak = ses.close()

    t0 = time.perf_counter()
    sampled = check.sample(run.answered, run.batches,
                           int(run.traffic["check_sample"]), seed)
    verdict = check.compare(run.requests, sampled, ses.ov, run.config,
                            run.config["check"], ses.reference)
    log(f"reference: {len(sampled)} answers recomputed in "
        f"{time.perf_counter() - t0:.3f} s")

    spec = ses.spec
    metrics = bench.read_metrics(
        spec["per_layer"] if trace else spec["end_to_end"], run)
    device = dict(ses.info, memory_peak_bytes=peak)
    out = {"correct": bool(verdict["ok"]),
           "attempted": len(run.requests),
           "failed": sum(1 for r in run.requests
                         if math.isinf(r.latency_s)),
           "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["check"] = verdict["numbers"]
    check.print_lines(verdict)
    return out


def compile_cache(root: str = bench.CHECKOUT) -> str:
    """Keep JAX's persistent compilation cache, every program in it, at
    the checkout's fixed ``.jax_cache`` (the program's own default too),
    whatever cache directory or size the environment sets: a cap on
    its size would evict a cell's programs (about 25 MB each) before
    the next run reads them.  Call before JAX is imported."""
    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    return path


def main(t_start: float, argv=None) -> int:
    """``run.py``'s command line; ``t_start`` is when the process began."""
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--precision", default=None,
                    help="run the program at this precision instead of "
                         "the configuration's (the control)")
    args = ap.parse_args(argv)
    compile_cache()
    spec = bench.cell(bench.load(), args.workload)
    info = device_info(int(spec["cell"]["chips"]))
    sys.path.insert(0, os.path.join(bench.CHECKOUT, "src"))
    out = run_cell(args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start=t_start,
                   precision=args.precision, info=info)
    import json
    print(json.dumps(out), flush=True)
    return 0
