"""Serving entrypoints: always-on overlay query serving + LM decode.

Two subcommands share this launcher:

``overlay`` — the paper-shaped service: a long-lived
:class:`repro.engine.QueryServer` hosting warm ``SimEngine`` instances
(one per requested topology), dynamically batching concurrent
``QuerySpec`` streams onto shared jitted sweeps and reporting serving
metrics (throughput, latency percentiles, batch histogram).

  PYTHONPATH=src python -m repro.launch.serve overlay \
      --topology ba --n-peers 2000 --backend jax \
      --policies fd-dynamic,cn --requests 256 --concurrency 16

``decode`` — the LM end-to-end path: prefill + decode where every decode
step executes a Top-k "query" over the model-sharded vocab axis using
the FD merge-and-backward.  ``--policy`` selects a member of the
``repro.engine`` registry (``fd-dynamic`` / ``cn`` / ``cn-star``); the
legacy ``--algorithm cn|cn_star`` flag still works and is mapped onto a
policy (benchmarks/tpu_comm uses this).

  PYTHONPATH=src python -m repro.launch.serve decode --arch qwen2-0.5b \
      --smoke --batch 4 --prompt-len 32 --gen 16

Flag-style invocations without a subcommand (``... serve --arch ...``)
keep routing to ``decode`` for back compatibility.
"""
from __future__ import annotations

import argparse
import time


def state_from_prefill(cfg, prefill_state, s_max: int,
                       cache_dtype=None):
    """Convert prompt-length caches into pre-sized decode caches (pad the
    seq dim to s_max; window caches wrap the last W positions)."""
    import jax
    import jax.numpy as jnp

    from repro.models import attention as A
    from repro.models import model as M

    if cache_dtype is None:
        cache_dtype = jnp.float32
    pos = int(prefill_state.pos)

    def _pad_seq(a, axis: int, target: int):
        """Pad/trim ``axis`` (negative index) of a to ``target`` length."""
        cur = a.shape[axis]
        if cur >= target:
            sl = [slice(None)] * a.ndim
            sl[axis] = slice(0, target)
            return a[tuple(sl)].astype(cache_dtype)
        cfg_pad = [(0, 0)] * a.ndim
        cfg_pad[a.ndim + axis] = (0, target - cur)
        return jnp.pad(a, cfg_pad).astype(cache_dtype)

    def conv(c):
        if isinstance(c, A.KVCache):
            return A.KVCache(_pad_seq(c.k, -3, s_max),
                             _pad_seq(c.v, -3, s_max))
        return c

    # window-attention archs need ring-buffer conversion; leading stacked
    # layer dims are folded into the batch dim first
    def conv_window(c, w):
        def fold(a):
            lead = a.shape[:-3]
            return a.reshape((-1,) + a.shape[-3:]), lead

        ks, lead = fold(c.k)
        vs, _ = fold(c.v)
        s = ks.shape[1]
        take = min(w, s, pos)
        lo = max(pos - take, 0)
        slots = (jnp.arange(lo, pos)) % w
        zk = jnp.zeros((ks.shape[0], w) + ks.shape[2:], cache_dtype)
        zv = jnp.zeros_like(zk)
        pos_slots = jnp.full((w,), -1, jnp.int32)
        zk = zk.at[:, slots].set(ks[:, lo:pos].astype(cache_dtype))
        zv = zv.at[:, slots].set(vs[:, lo:pos].astype(cache_dtype))
        pos_slots = pos_slots.at[slots].set(
            jnp.arange(lo, pos, dtype=jnp.int32))
        zk = zk.reshape(lead + zk.shape[1:])
        zv = zv.reshape(lead + zv.shape[1:])
        if len(lead) >= 2:      # scan-stacked groups carry (G, W) slots
            pos_slots = jnp.broadcast_to(pos_slots, (lead[0], w)).copy()
        return A.WindowKVCache(zk, zv, pos_slots)

    def walk(c):
        if isinstance(c, dict):
            out = {}
            for key, v in c.items():
                if key == "self" and isinstance(v, A.KVCache) \
                        and cfg.local_window:
                    out[key] = conv_window(v, cfg.local_window)
                elif isinstance(v, (A.KVCache, A.MLACache)):
                    out[key] = conv(v) if isinstance(v, A.KVCache) else \
                        _conv_mla(v, s_max, cache_dtype)
                else:
                    out[key] = v
            return out
        if isinstance(c, list):
            return [walk(x) for x in c]
        return c

    def _conv_mla(c, s_max, dt):
        return A.MLACache(_pad_seq(c.c_kv, -2, s_max),
                          _pad_seq(c.k_rope, -2, s_max))

    caches = jax.tree.map(lambda x: x, prefill_state.caches)  # copy struct
    caches = {"groups": [walk(g) for g in prefill_state.caches["groups"]],
              "rem": [walk(r) for r in prefill_state.caches["rem"]]}
    return M.DecodeState(caches, prefill_state.pos)


def main_overlay(argv=None):
    """Run a QueryServer over warm overlay engines and drive it with a
    closed-loop client pool; prints and returns the serving metrics."""
    import threading

    import numpy as np

    ap = argparse.ArgumentParser(prog="serve overlay")
    ap.add_argument("--topology", default="ba",
                    help="comma list of registered topology families "
                         "(one warm engine per entry)")
    ap.add_argument("--n-peers", type=int, default=1000)
    ap.add_argument("--backend", default="numpy",
                    choices=("numpy", "jax"))
    ap.add_argument("--policies", default="fd-dynamic,cn",
                    help="comma list of engine policy names, assigned "
                         "round-robin to requests")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--concurrency", type=int, default=16,
                    help="closed-loop client threads")
    ap.add_argument("--n-trials", type=int, default=1)
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--batch-window-ms", type=float, default=2.0)
    ap.add_argument("--timeout-s", type=float, default=None)
    args = ap.parse_args(argv)

    from repro.engine import QueryServer, QuerySpec, ServerConfig, SimEngine
    from repro.engine.serve import ServerError
    from repro.p2psim import SimParams, build_topology

    params = SimParams(k=args.k)
    engines = {}
    for fam in args.topology.split(","):
        fam = fam.strip()
        topo = build_topology(fam, args.n_peers, seed=args.seed)
        engines[fam] = SimEngine(topo, params=params,
                                 backend=args.backend)
    policies = [p.strip() for p in args.policies.split(",")]
    names = sorted(engines)
    server = QueryServer(engines, ServerConfig(
        max_queue=args.max_queue, max_batch=args.max_batch,
        batch_window_s=args.batch_window_ms / 1e3,
        default_timeout_s=args.timeout_s))
    for name in names:      # populate plan / jit caches before load
        server.warm(QuerySpec(origins=(0,), seed=args.seed),
                    policies[0], engine=name)

    rng = np.random.default_rng(args.seed)
    reqs = [(QuerySpec(origins=(int(rng.integers(args.n_peers)),),
                       n_trials=args.n_trials,
                       seed=int(rng.integers(1 << 30))),
             policies[i % len(policies)], names[i % len(names)])
            for i in range(args.requests)]
    cursor = {"i": 0}
    lock = threading.Lock()
    errors = []

    def client():
        while True:
            with lock:
                i = cursor["i"]
                if i >= len(reqs):
                    return
                cursor["i"] = i + 1
            spec, pol, name = reqs[i]
            try:
                server.query(spec, pol, engine=name)
            except ServerError as e:     # shed/timeout: counted, not fatal
                errors.append(e)

    with server:
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client)
                   for _ in range(args.concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        m = server.metrics()
    qps = m.served / max(wall, 1e-9)
    print(f"served {m.served}/{args.requests} requests over "
          f"{len(engines)} engine(s) [{args.backend}] in {wall:.2f}s "
          f"({qps:.1f} qps); shed {m.shed}, timed out {m.timed_out}")
    if m.latency is not None:
        print("latency p50/p95/p99 = "
              f"{m.latency.p50_s * 1e3:.2f}/{m.latency.p95_s * 1e3:.2f}/"
              f"{m.latency.p99_s * 1e3:.2f} ms; mean batch "
              f"{m.mean_batch:.2f} (max {m.max_batch})")
    metrics = m.as_dict()
    metrics["wall_s"] = wall
    metrics["throughput_qps"] = qps
    return metrics


def main_decode(argv=None):
    """LM prefill + decode driver (FD top-k sampling each step)."""
    ap = argparse.ArgumentParser(prog="serve decode")
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--policy", default=None,
                    help="engine policy name (fd-dynamic / cn / cn-star; "
                         "see repro.engine); overrides --algorithm")
    ap.add_argument("--algorithm", default="fd",
                    choices=("fd", "cn", "cn_star"),
                    help="legacy algorithm flag (mapped onto a policy)")
    ap.add_argument("--schedule", default="halving",
                    choices=("halving", "doubling", "ring"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from repro import jaxcompat
    from repro.configs.base import get_config, smoke_config
    from repro.data.pipeline import extra_model_inputs
    from repro.engine import get_policy, policy_from_legacy
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.optim.sharding import batch_axes, param_specs
    from repro.runtime.steps import make_serve_step

    try:
        pol = (get_policy(args.policy) if args.policy
               else policy_from_legacy(args.algorithm))
    except KeyError as e:
        raise SystemExit(f"--policy: {e.args[0]}")
    if pol.algorithm not in ("fd", "cn", "cn_star"):
        raise SystemExit(f"policy {pol.name!r} has no device backend")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    mesh = make_host_mesh(model=args.model_par)
    ctx = jaxcompat.use_mesh(mesh)
    ctx.__enter__()
    s_max = args.prompt_len + args.gen

    key = jax.random.PRNGKey(0)
    params_abs = jax.eval_shape(
        lambda k: M.init_params(k, cfg, max_seq=s_max), key)
    pspecs = param_specs(params_abs, cfg, mesh)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    params = jax.jit(lambda k: M.init_params(k, cfg, max_seq=s_max),
                     out_shardings=pshard)(key)

    rng = np.random.default_rng(0)
    batch_np = {"tokens": rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)}
    batch = extra_model_inputs(cfg, batch_np)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}

    t0 = time.time()
    last_logits, pstate = M.prefill(params, cfg, batch)
    state = state_from_prefill(cfg, pstate, s_max)
    t_prefill = time.time() - t0

    baxes = batch_axes(dict(mesh.shape))
    serve_step = jax.jit(
        make_serve_step(cfg, mesh, k=args.k, algorithm=pol.algorithm,
                        schedule=args.schedule, batch_axes=baxes),
        donate_argnums=(1,))

    tok = jnp.argmax(last_logits, axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    key = jax.random.PRNGKey(1)
    t0 = time.time()
    for i in range(args.gen - 1):
        key, sub = jax.random.split(key)
        tok, state = serve_step(params, state, tok, sub)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    toks = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    print(f"arch={cfg.name} policy={pol.name} "
          f"prefill {args.prompt_len} tok in {t_prefill:.2f}s; "
          f"decoded {args.gen - 1} steps in {t_decode:.2f}s "
          f"({(args.gen - 1) * args.batch / max(t_decode, 1e-9):.1f} tok/s)")
    print("sample tokens:", toks[0, :12].tolist())
    ctx.__exit__(None, None, None)
    return toks


def main(argv=None):
    """Dispatch ``overlay`` / ``decode``; bare flags route to decode."""
    import sys

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    if argv and argv[0] == "overlay":
        return main_overlay(argv[1:])
    if argv and argv[0] == "decode":
        return main_decode(argv[1:])
    return main_decode(argv)            # legacy flag-style invocation


if __name__ == "__main__":
    main()
