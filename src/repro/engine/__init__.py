"""Unified Top-k query engine: QuerySpec + Policy registry + compiled
NetworkPlan, across the sim and device backends.

    from repro.engine import SimEngine, QuerySpec

    engine = SimEngine(topology)            # compiles a NetworkPlan once
    res = engine.run(QuerySpec(origins=(0, 7), n_trials=4), "fd-dynamic")
    res.metrics.summary()                   # per-entry BatchMetrics

    engine.run(QuerySpec(origins=(0, 7)), "cn-star")   # plan reused

    SimEngine(topology, backend="jax")      # jitted XLA sweeps — same
                                            # bits, 100k-peer scale

``SimEngine(backend="jax")`` lowers the forward and merge sweeps to
jitted JAX over the plan's cached ``DepthSlices`` (``sim_jax`` is
imported lazily, so the default numpy path traces and compiles
nothing; the package imports JAX only for the profiler's trace spans);
``DeviceEngine`` exposes the same surface over the JAX shard_map
collectives (also imported lazily).

For sustained concurrent load, ``QueryServer`` hosts warm engines
behind a bounded queue and a dynamic batcher that coalesces compatible
requests onto one sweep via ``Engine.run_many`` (see docs/SERVING.md):

    with QueryServer(SimEngine(topology, backend="jax")) as server:
        handle = server.submit(QuerySpec(origins=(0,)), "fd-dynamic")
        res = handle.result()
"""
from repro.engine.api import (Engine, Policy, QuerySpec,  # noqa: F401
                              TopKResult, available_policies, get_policy,
                              policy_from_legacy, register_policy)
from repro.engine.plan import NetworkPlan  # noqa: F401
from repro.engine.serve import (LatencyStats, PhaseStats,  # noqa: F401
                                QueryHandle, QueryServer, RequestTimeout,
                                ServerClosed, ServerConfig, ServerError,
                                ServerMetrics, ServerOverloaded)
from repro.engine.sim import SimEngine  # noqa: F401
from repro.p2psim.overlay import (Overlay, SessionEvent,  # noqa: F401
                                  apply_events, available_repairs,
                                  get_repair, random_session,
                                  register_repair)

__all__ = ["QuerySpec", "Policy", "TopKResult", "NetworkPlan", "Engine",
           "SimEngine", "DeviceEngine", "QueryServer", "QueryHandle",
           "ServerConfig", "ServerError", "ServerOverloaded",
           "RequestTimeout", "ServerClosed", "ServerMetrics",
           "LatencyStats", "PhaseStats", "Overlay", "SessionEvent",
           "random_session", "apply_events", "available_policies",
           "get_policy", "policy_from_legacy", "register_policy",
           "register_repair", "get_repair", "available_repairs"]


def __getattr__(name):
    """Resolve the lazy ``DeviceEngine`` export (imports JAX)."""
    if name == "DeviceEngine":
        from repro.engine.device import DeviceEngine
        return DeviceEngine
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
