"""SimEngine ``backend="jax"`` — jitted overlay sweeps at 100k-peer scale.

The numpy engine's two hot phases are lowered to XLA:

  * the per-depth forward-phase sweep — query arrival times down the
    BFS tree plus the Strategy-1 "who-sent-first" edge reduction; the
    per-level gather+add is the fused jnp expression and the Appendix-A
    wait-propagation rule routes through ``repro.kernels.sweep`` (jnp
    oracle, or the Pallas kernel with ``use_pallas``);
  * the bottom-up k-list merge — the static fold schedule compiled into
    the plan's :class:`~repro.engine.plan.DepthSlices` executes only
    real pairwise merges (plus odd-slot carries), each one a fused
    bitonic merge network (max against the reversed partner, then
    log2(K) compare-exchange stages) — no ``top_k``, no sorts, no
    scatters, which XLA:CPU punishes by orders of magnitude.  With
    ``use_pallas`` the pairwise step routes through the Pallas bitonic
    kernel in ``repro.kernels.merge`` instead (the same network).

Kernel selection (``select_pallas``): ``use_pallas=None`` is the fused
jnp path on every platform — no chip measurement has yet shown a
kernel beating it.  ``use_pallas=True`` compiles the Pallas kernels on
TPU for f32 / bf16 sweeps; float64 never enters a Mosaic kernel — the
TPU has no native f64 — so ``use_pallas=True`` with f64 on TPU raises.
Off-TPU, ``use_pallas=True`` runs the kernels in the Pallas interpreter
(the CPU test path); nothing on the chip ever interprets
(``repro.kernels.platform``).

Everything stochastic is precomputed in numpy by the SHARED
``_precompute_draws`` (same RNG streams, same order as the scalar
reference), and the retrieval / accuracy epilogue is the shared numpy
code — so this backend is bit-for-bit equal to the numpy backend in
every RNG mode, and therefore to ``run_query_reference`` wherever the
numpy backend is (shared batch of one, independent streams).  With the
default ``precision="f64"`` the sweeps trace and run inside
``jax.enable_x64()`` (scoped to the calling thread): float64 is what
makes "same expression" mean "same bits".

Reduced precision (``precision="f32"`` / ``"bf16"``) casts the shared
numpy draws once on the host and runs the forward sweep and merge
folds in that dtype end to end — no silent upcast anywhere (the merge
kernels preserve f32/bf16) — trading the bit contract for the
tolerance contract checked by :mod:`repro.engine.precision`: top-k
recall against the f64 ground truth plus an rtol bound on the scores.
The epilogue containers stay float64 (upcasts are exact), and the
ground-truth top-k is computed from the CAST scores so value matching
in the retrieval epilogue stays consistent with what the sweep saw.

Entry batches are padded to the next power of two (the pad rows repeat
a real entry; rows are independent, outputs are sliced back), so the
jit cache keys on size buckets instead of exact entry counts — a
serving workload with mixed fused batch sizes stops retracing per
shape.  Per-sweep compile time is measured (cache-miss detection via
the jit cache size) and returned as ``jax_compile_s`` / ``jax_traces``
so the serving layer can attribute latency honestly.  On accelerators
the five per-entry draw buffers are donated to the sweep — the level
arrays they produce replace them instead of doubling resident memory
across depth levels (donation is a no-op on CPU and is disabled
there; the choice is made at the first sweep call, not at import).

``shard=True`` runs the same sweep through ``shard_map`` over all
local devices on the batch-entry axis (the ``jaxcompat`` mesh helpers
the multi-device :class:`~repro.engine.device` collectives are built
on): entries are embarrassingly parallel, so
each device materializes only its slice of the (entries, n) working
set — that is what lets a million-peer plan's sweep fit when a single
host's slice would not.

Churn (finite ``lifetime_mean_s``, §4/§5.4) runs end-to-end in the
same jitted sweep — no numpy fallback:

  * exponential death times come from the SHARED numpy draws
    (``EntryDraws.death``), so the stochastic inputs stay bit-identical
    across backends;
  * a peer dead at its send time contributes ``inf`` arrivals and
    ``-inf`` k-list rows — pure masks, no data-dependent shapes;
  * §4.2 dead-parent rerouting folds over the plan's STATIC reroute
    candidate tables (``DepthSlices`` with ``reroute=True``): every
    grandchild is a fixed slot in an augmented merge schedule whose
    per-entry liveness mask ("my parent died, I did not") decides at
    run time whether it contributes — fixed-shape gather/select, like
    everything else here;
  * urgent-list forwarding (§4.1) and the reroute message accounting
    stay in the shared numpy epilogue, computed from the ``alive`` masks
    the sweep returns.

Copy back: only what that epilogue reads leaves the device — the send,
list-arrival and liveness rows of the reached peers, packed in level
order, and each origin's merged list.  Every peer's k-list stays on the
device; the few the origin folds in as accepted urgent lists are
gathered on demand (``_urgent_rows``).
"""
from __future__ import annotations

import contextlib
import functools
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import jaxcompat
from repro.engine.plan import DepthSlices, NetworkPlan
from repro.engine.precision import np_dtype
from repro.kernels.merge.merge import _next_pow2
from repro.kernels.merge.ops import merge_scorelists
from repro.kernels.platform import on_tpu
from repro.kernels.sweep import arrivals_ref, wait_propagate
from repro.p2psim.metrics import ENTRY_BYTES_PAPER
from repro.p2psim.simulate import (SimParams, _accept_urgent_origin,
                                   _cn_entries, _empty_out,
                                   _entry_latencies, _precompute_draws,
                                   _reroute_counts, _retrieval_exact,
                                   _retrieval_shared,
                                   _true_topk_by_origin, _urgent_accepted,
                                   wait_time)


def _merge_desc(va, ia, vb, ib, valid_a=None, valid_b=None):
    """Fused bitonic merge of two descending K-lists (K a power of two).

    ``max(a_i, reverse(b)_i)`` selects the top-K multiset of the union
    as a bitonic sequence; log2(K) half-cleaner stages re-sort it
    descending.  Pure elementwise min/max/select — XLA fuses the whole
    network into one pass.  Each stage is a true compare-exchange, so
    the output holds each input entry at most once: on tied scores it
    is still a top-k of the union with ``merge_ref``'s values, though
    which tied entries survive the cut at k, and their order, may
    differ from ``merge_ref``'s.

    ``valid_a`` / ``valid_b``: optional row masks — an invalid list
    (late child, churned-out peer, live-parent reroute slot) becomes
    -inf rows, which real scores always beat, so validity costs one
    fused select instead of a branch.
    """
    if valid_a is not None:
        va = jnp.where(valid_a[..., None], va, -jnp.inf)
    if valid_b is not None:
        vb = jnp.where(valid_b[..., None], vb, -jnp.inf)
    K = va.shape[-1]
    fb = vb[..., ::-1]
    fo = ib[..., ::-1]
    take = va >= fb
    v = jnp.where(take, va, fb)
    o = jnp.where(take, ia, fo)
    lane = np.arange(K)
    s = K // 2
    while s >= 1:
        # partner exchange via reshape+reverse (fusible, unlike stack):
        # lane l swaps with l ^ s inside each 2s block
        shp = v.shape[:-1] + (K // (2 * s), 2, s)
        vp = jnp.flip(v.reshape(shp), axis=-2).reshape(v.shape)
        op = jnp.flip(o.reshape(shp), axis=-2).reshape(o.shape)
        # a true compare-exchange: the low lane keeps its score unless
        # the partner's is strictly larger and the high lane the mirror,
        # so equal scores stay put and no owner is copied over another
        take_max = jnp.asarray(lane % (2 * s) < s)
        keep = jnp.where(take_max, v >= vp, v <= vp)
        v = jnp.where(keep, v, vp)
        o = jnp.where(keep, o, op)
        s //= 2
    return v, o


def _merge_lists(va, ia, vb, ib, use_pallas: bool,
                 valid_a=None, valid_b=None):
    """One pairwise descending k-list merge (top-k of the union)."""
    if use_pallas:
        return merge_scorelists(va, ia, vb, ib, use_pallas=True,
                                valid_a=valid_a, valid_b=valid_b)
    return _merge_desc(va, ia, vb, ib, valid_a, valid_b)


def _retire(pools, ret, ret_perm, valid=None):
    """Gather each finished segment's slot, in parent-ascending order.

    ``valid``: slot mask over the ROUND-0 pool.  Only round-0
    retirements (single-slot segments) can surface a never-merged input
    slot, so that is the only place the mask applies — every later
    retirement is a merge output, already mask-resolved.
    """
    parts = []
    for r, idx in enumerate(ret):
        if idx is None:
            continue
        seg = pools[r][:, idx]
        if valid is not None and r == 0:
            m = valid[:, idx]
            seg = jnp.where(m[..., None] if seg.ndim == 3 else m,
                            seg, -jnp.inf)
        parts.append(seg)
    return jnp.concatenate(parts, axis=1)[:, ret_perm]


def _fold_lists(cv, co, sched, use_pallas, valid=None):
    """Run the static fold schedule ``sched = (rounds, ret, ret_perm)``
    over the child (and, in churn mode, reroute-candidate) k-lists;
    returns each parent's merged top-k, in parent-ascending order.

    ``valid``: per-slot liveness over round 0's slots.  The mask is
    THREADED through the fold — merge inputs mask at the kernel, merge
    outputs are always valid, carried slots inherit — so no masked copy
    of the full child array is ever materialized.
    """
    rounds, ret, ret_perm = sched
    pools_v, pools_o = [cv], [co]
    vm = valid
    for mi_a, mi_b, pi in rounds:
        ma = mb = None
        if vm is not None:
            ma, mb = vm[:, mi_a], vm[:, mi_b]
        mv, mo = _merge_lists(cv[:, mi_a], co[:, mi_a],
                              cv[:, mi_b], co[:, mi_b], use_pallas,
                              ma, mb)
        if pi.shape[0]:
            mv = jnp.concatenate([mv, cv[:, pi]], axis=1)
            mo = jnp.concatenate([mo, co[:, pi]], axis=1)
            if vm is not None:
                vm = jnp.concatenate(
                    [jnp.ones(mv.shape[:1] + (mi_a.shape[0],), bool),
                     vm[:, pi]], axis=1)
        elif vm is not None:
            vm = jnp.ones(mv.shape[:2], bool)
        cv, co = mv, mo
        pools_v.append(mv)
        pools_o.append(mo)
    return (_retire(pools_v, ret, ret_perm, valid),
            _retire(pools_o, ret, ret_perm))


def _fold_max(a, lv):
    """Child-slot schedule, max-reduce: each parent's latest child
    arrival (dead children carry ``inf`` — the paper's waiting parent
    can only be released by its deadline)."""
    pools = [a]
    for mi_a, mi_b, pi in lv["rounds"]:
        ma = jnp.maximum(a[:, mi_a], a[:, mi_b])
        if pi.shape[0]:
            ma = jnp.concatenate([ma, a[:, pi]], axis=1)
        a = ma
        pools.append(ma)
    return _retire(pools, lv["ret"], lv["ret_perm"])


def _fd_sweep_impl(scores, t_exec, up_term, dn_term, death, wt, tqf, lam,
                   levels, els, rr, *, k, use_pallas, with_st1,
                   with_churn, with_reroute):
    """Forward + merge-and-backward sweeps of one origin's tree.

    Per-level functional form: level d's arrays are produced from level
    d±1's by static gathers — nothing is scattered into a global
    buffer.  Bit-parity contract (f64): every float expression groups
    exactly as the numpy sweep's; k-lists are padded to
    K = 2^ceil(log2 k) with -inf tails that never surface in the top k.
    In reduced precision every intermediate inherits the input dtype —
    the literal zero / -inf buffers below are created in the operand
    dtype precisely so no f32/bf16 value is ever silently upcast.

    The Appendix-A wait rule dispatches through ``repro.kernels.sweep``
    — the jnp oracle or the Pallas kernel depending on ``use_pallas``
    (same bits either way).

    Churn (``with_churn``): a peer dead at its would-be send time gets
    ``send = inf`` (its arrival can never release a waiting parent) and
    -inf / -1 merged rows — the exact fill the numpy sweep commits.
    ``with_reroute`` additionally folds each level's static grandchild
    table (``rr_*``): a grandchild slot is live iff its parent died and
    it did not, which reproduces §4.2's "children of a dead peer send
    their lists to the grandparent".  All of it is masks over fixed
    shapes; the one scalar the masks hinge on — the peer's death time —
    comes from the shared numpy draws.

    Each level's list-arrival times at the parent (``send + up_term``)
    are returned with the send times, so the host's urgent-list pass
    reads the very values the on-time mask compared rather than
    re-adding them in its own arithmetic (which differs from an
    emulated device float64 in the last bits, and would turn every
    child that released its parent into a phantom late arrival).

    Returns ``(send, arr, alive, origin_v, origin_o, skip, m_v, m_o)``:
    the send times, list-arrival times (levels 1 and deeper; the origin
    sends no list) and, under churn, liveness of every reached peer,
    each (E, reached) packed along the peer axis in level order
    (``_level_order``); the origin's merged list (E, k); the Strategy-1
    skip counts (or None); and every level's merged lists (E, L, k).
    The host copies back only the packed rows and the origin's list;
    the level lists stay on the device for ``_urgent_rows``.
    """
    E = t_exec.shape[0]
    K = _next_pow2(k)
    dmax = len(levels) - 1

    skip = None
    if with_st1:
        els_src, els_dst, cond = els
        send_at = tqf[None, :] + lam
        skip = ((send_at[:, els_dst] < send_at[:, els_src])
                & cond[None, :]).sum(axis=1)

    t_qs = [jnp.zeros((E, 1), t_exec.dtype)]
    for d in range(1, dmax + 1):
        lv = levels[d]
        t_qs.append(arrivals_ref(t_qs[d - 1], dn_term[:, lv["vv"]],
                                 lv["par_pos"]))

    send = [None] * (dmax + 1)
    arr = [None] * (dmax + 1)
    m_v = [None] * (dmax + 1)
    m_o = [None] * (dmax + 1)
    alive = [None] * (dmax + 1)
    for d in range(dmax, -1, -1):
        lv = levels[d]
        vv = lv["vv"]
        L = vv.shape[0]
        own_ready = t_qs[d] + t_exec[:, vv]
        deadline = t_qs[d] + wt[vv][None, :]
        death_lv = death[:, vv] if with_churn else None
        own_v = scores[:, vv]
        if K > k:
            own_v = jnp.concatenate(
                [own_v, jnp.full((E, L, K - k), -jnp.inf, own_v.dtype)],
                axis=2)
        own_o = jnp.broadcast_to(vv.astype(jnp.int32)[None, :, None],
                                 (E, L, K))
        a0 = None
        if "cnode" not in lv:                    # all leaves
            all_in = jnp.zeros((E, L), own_ready.dtype)
        else:
            a0 = arr[d + 1][:, lv["c_in_next"]]
            # the parent's send time (needed for the on-time mask)
            # depends on all_in, a pure max over ALL child arrivals
            # (dead children contribute inf) — mask-free, exactly as
            # numpy computes it
            n_par = lv["ret_perm"].shape[0]
            am = _fold_max(a0, lv)
            all_in = jnp.concatenate(
                [am, jnp.zeros((E, L - n_par), am.dtype)],
                axis=1)[:, lv["asm_perm"]]
        if with_churn:
            s, snd = wait_propagate(own_ready, all_in, deadline,
                                    death=death_lv,
                                    use_pallas=use_pallas)
        else:
            s = wait_propagate(own_ready, all_in, deadline,
                               use_pallas=use_pallas)
        if a0 is None:
            mv, mo = own_v, own_o
        else:
            # on-time = arrived by the parent's (raw) send time; a dead
            # child's a0 is inf, so validity is already folded in
            ont = a0 <= s[:, lv["cpar_pos"]]
            cv0 = m_v[d + 1][:, lv["c_in_next"]]
            co0 = m_o[d + 1][:, lv["c_in_next"]]
            vmask = ont
            sched = (lv["rounds"], lv["ret"], lv["ret_perm"])
            if with_reroute and rr[d] is not None:
                # §4.2 reroute slots: level-(d+2) lists contribute to
                # their grandparent iff their parent died (their own
                # death is already folded into m_v's -inf rows)
                gv = m_v[d + 2][:, rr[d]["gc_pos"]]
                go = m_o[d + 2][:, rr[d]["gc_pos"]]
                gmask = ~alive[d + 1][:, rr[d]["gc_par_pos"]]
                cv0 = jnp.concatenate([cv0, gv], axis=1)
                co0 = jnp.concatenate([co0, go], axis=1)
                vmask = jnp.concatenate([ont, gmask], axis=1)
                sched = (rr[d]["rounds"], rr[d]["ret"],
                         rr[d]["ret_perm"])
            child_v, child_o = _fold_lists(cv0, co0, sched, use_pallas,
                                           valid=vmask)
            pv, po = _merge_lists(own_v[:, lv["par_sel"]],
                                  own_o[:, lv["par_sel"]],
                                  child_v, child_o, use_pallas)
            mv = jnp.concatenate(
                [pv, own_v[:, lv["leaf_sel"]]], axis=1)[:, lv["asm_perm"]]
            mo = jnp.concatenate(
                [po, own_o[:, lv["leaf_sel"]]], axis=1)[:, lv["asm_perm"]]
        if with_churn:
            alv = death_lv >= s
            alive[d] = alv
            send[d] = snd
            m_v[d] = jnp.where(alv[..., None], mv, -jnp.inf)
            m_o[d] = jnp.where(alv[..., None], mo, -1)
        else:
            send[d] = s
            m_v[d], m_o[d] = mv, mo
        if d:
            arr[d] = send[d] + up_term[:, vv]
    m_v = tuple(v[:, :, :k] for v in m_v)
    m_o = tuple(o[:, :, :k] for o in m_o)
    arr_p = (jnp.concatenate(arr[1:], axis=1) if dmax
             else jnp.zeros((E, 0), send[0].dtype))
    return (jnp.concatenate(send, axis=1), arr_p,
            jnp.concatenate(alive, axis=1) if with_churn else None,
            m_v[0][:, 0], m_o[0][:, 0], skip, m_v, m_o)


_SWEEP_STATICS = ("k", "use_pallas", "with_st1", "with_churn",
                  "with_reroute")


@functools.lru_cache(maxsize=None)
def _fd_sweep():
    """The jitted sweep, built at first use.

    Buffer donation: each call converts fresh host draws to device
    buffers; donating the five big per-entry operands lets XLA reuse
    their memory for the level outputs instead of holding both live
    across the whole depth loop.  CPU XLA does not implement donation
    (it would only warn), so it is enabled on accelerators only — a
    decision that needs the backend, hence not made at import.
    """
    donate = () if jax.default_backend() == "cpu" else (0, 1, 2, 3, 4)
    return jax.jit(_fd_sweep_impl, static_argnames=_SWEEP_STATICS,
                   donate_argnums=donate)


@functools.lru_cache(maxsize=None)
def _sharded_fd_sweep(n_dev: int, k: int, use_pallas: bool,
                      with_st1: bool, with_churn: bool,
                      with_reroute: bool):
    """``_fd_sweep`` sharded over the batch-entry axis on all devices.

    Entries are embarrassingly parallel (each row is one query trial on
    its own tree), so ``shard_map`` splits every (entries, n) operand
    across a 1-D device mesh and each device runs the identical sweep
    on its slice — no collectives needed, and no device ever
    materializes the full working set.  Static tables (wait budgets,
    level slices, fold schedules) are replicated; the per-entry draws
    are split.  Built with the same ``jaxcompat`` mesh/shard_map
    helpers as the ``DeviceEngine`` collectives.
    """
    P = jax.sharding.PartitionSpec
    mesh = jaxcompat.make_mesh((n_dev,), ("entries",))
    ent, rep = P("entries"), P()
    fn = functools.partial(_fd_sweep_impl, k=k, use_pallas=use_pallas,
                           with_st1=with_st1, with_churn=with_churn,
                           with_reroute=with_reroute)
    in_specs = (ent, ent, ent, ent,          # scores..dn_term
                ent if with_churn else rep,  # death (or empty stub)
                rep, rep,                    # wt, tqf
                ent if with_st1 else rep,    # lam (or empty stub)
                rep, rep, rep)               # levels, els, rr
    sharded = jaxcompat.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                  out_specs=ent,
                                  axis_names=("entries",))
    return jax.jit(sharded)


@jax.jit
def _cn_sweep(t_exec, dn_term, levels):
    """CN / CN* need only the arrival sweep: t_exec_done per level."""
    E = t_exec.shape[0]
    t_qs = [jnp.zeros((E, 1), t_exec.dtype)]
    for d in range(1, len(levels)):
        lv = levels[d]
        t_qs.append(t_qs[d - 1][:, lv["par_pos"]]
                    + dn_term[:, lv["vv"]])
    return tuple(tq + t_exec[:, lv["vv"]]
                 for tq, lv in zip(t_qs, levels))


URGENT_CHUNK = 16          # rows per ``_urgent_rows`` call (fixed shape)


@jax.jit
def _urgent_rows(m_v, m_o, lvl, pos, row):
    """The merged lists of accepted urgent children, gathered from one
    sweep's device-resident level outputs ``m_v`` / ``m_o``.

    Row i is the list at level ``lvl[i]``, position ``pos[i]`` inside
    that level, entry row ``row[i]``; ``lvl`` -1 pads (-inf / -1 rows).
    The index has a fixed length (``URGENT_CHUNK``), so one program
    serves every call on the same outputs' shapes.
    """
    v = jnp.full(lvl.shape + m_v[0].shape[-1:], -jnp.inf, m_v[0].dtype)
    o = jnp.full(v.shape, -1, m_o[0].dtype)
    for d in range(1, len(m_v)):         # level 0 is the origin itself
        hit = (lvl == d)[:, None]
        p = jnp.minimum(pos, m_v[d].shape[1] - 1)
        v = jnp.where(hit, m_v[d][row, p], v)
        o = jnp.where(hit, m_o[d][row, p], o)
    return v, o


def _conv_slice_field(f, v):
    if f.endswith("rounds"):
        return tuple(tuple(jnp.asarray(x) for x in rnd) for rnd in v)
    if f.endswith("ret"):
        return tuple(None if idx is None else jnp.asarray(idx)
                     for idx in v)
    return jnp.asarray(v)


def _device_slices(sl: DepthSlices):
    """DepthSlices as cached device arrays (one transfer per plan).

    The reroute (``rr_*``) tables are cached SEPARATELY and returned as
    their own per-level tuple: the static sweep's ``levels`` pytree
    never changes shape when a plan later serves churn policies, so its
    jit traces and device uploads stay valid.
    """
    cached = getattr(sl, "_device", None)
    if cached is None:
        levels = tuple({f: _conv_slice_field(f, v) for f, v in lv.items()
                        if not f.startswith("rr_")} for lv in sl.levels)
        els = (jnp.asarray(sl.els_src), jnp.asarray(sl.els_dst),
               jnp.asarray(sl.cond))
        cached = sl._device = (levels, els)
    rr = getattr(sl, "_device_rr", None)
    if rr is None and sl.reroute:
        rr = sl._device_rr = tuple(
            {f[3:]: _conv_slice_field(f, lv[f])
             for f in ("rr_gc_pos", "rr_gc_par_pos", "rr_rounds",
                       "rr_ret", "rr_ret_perm")}
            if "rr_rounds" in lv else None
            for lv in sl.levels)
    return cached + (rr,)


def _level_order(sl: DepthSlices):
    """The sweep's packed peer order (levels in turn, each ascending)
    and every reached peer's position inside its own level, cached on
    the slices beside their device copy."""
    cached = getattr(sl, "_order", None)
    if cached is None:
        order = np.concatenate([lv["vv"] for lv in sl.levels])
        pos = np.zeros(sl.n, np.int32)
        for lv in sl.levels:
            pos[lv["vv"]] = np.arange(len(lv["vv"]))
        cached = sl._order = (order, pos)
    return cached


def _to_host(x, nbytes: list) -> np.ndarray:
    """Copy a device array to the host, noting its size in ``nbytes``."""
    nbytes.append(x.nbytes)
    return np.asarray(x)


def _cache_entries(fn) -> int:
    """Size of a jitted function's trace cache (-1 when unknowable)."""
    try:
        return fn._cache_size()
    except Exception:
        return -1


def _compiled(out: dict, fn, *args, **kw):
    """Call a jitted program through ``block_until_ready``; attribute
    its wall time to ``out["jax_compile_s"]`` when the call actually
    traced (jit cache grew).  Returns the result and whether it
    traced."""
    before = _cache_entries(fn)
    t0 = time.perf_counter()
    res = fn(*args, **kw)
    jax.block_until_ready(res)
    wall = time.perf_counter() - t0
    after = _cache_entries(fn)
    traced = after > before >= 0
    if traced:
        out["jax_compile_s"] += wall
        out["jax_traces"] += after - before
    return res, traced


def _pad_group(es: np.ndarray, E: int, n_dev: int):
    """Pad an entry group to its size bucket (next power of two,
    rounded up to a device-mesh multiple).

    Entry rows are independent, so the pad rows just repeat a real
    entry and the sweep outputs are sliced back to ``len(es)``; the
    jit cache then keys on O(log E) bucket sizes instead of every
    distinct fused batch size the serving layer produces.

    Returns ``(es_run, full)`` — ``full`` means "the group IS the whole
    batch, in order", letting callers skip the gather entirely.
    """
    m = len(es)
    B = _next_pow2(max(m, 1))
    if n_dev > 1:
        B = -(-B // n_dev) * n_dev
    if B == m:
        return es, m == E
    return np.concatenate([es, np.repeat(es[-1:], B - m)]), False


def select_pallas(use_pallas: Optional[bool], precision: str) -> bool:
    """Whether the sweep runs the Pallas kernels.

    ``None`` (the default) is the fused jnp path on every platform: no
    chip run has yet shown a kernel beating it (ROADMAP S5).  ``True``
    compiles the kernels on TPU for f32 / bf16 and runs them in the
    interpreter off-TPU; ``True`` on TPU with f64 raises — float64
    never enters a Mosaic kernel.
    """
    if use_pallas and on_tpu() and precision == "f64":
        raise ValueError(
            "use_pallas=True with precision='f64' on TPU: float64 never "
            "enters a Mosaic kernel; use use_pallas=None (the fused jnp "
            "path) or precision='f32'/'bf16'")
    return bool(use_pallas)


def run_entries_jax(plan: NetworkPlan, sts, ent_st: np.ndarray,
                    ent_origin: np.ndarray, seeds, n: int, p: SimParams,
                    algorithm: str, dynamic: bool, lifetime_mean_s: float,
                    independent: bool,
                    use_pallas: Optional[bool] = None,
                    replicas=None, precision: str = "f64",
                    shard: bool = False) -> dict:
    """Drop-in for the numpy ``_run_entries`` with jitted sweeps.

    Same contract, same outputs — and with the default
    ``precision="f64"`` the same bits; see the module docstring.
    ``precision="f32"`` / ``"bf16"`` runs the sweeps in reduced
    precision (tolerance contract).  ``shard=True`` splits the entry
    batch across all local devices via ``shard_map``.  Finite
    ``lifetime_mean_s`` (churn) runs in the same jitted sweep; there
    is no numpy fallback.  The returned dict carries two scalar
    side-channels next to the per-entry arrays: ``jax_compile_s`` (wall
    time of sweep calls that actually traced) and ``jax_traces``.
    """
    churn = not math.isinf(lifetime_mean_s)
    E = len(seeds)
    S = len(sts)
    k = p.k
    list_bytes = k * ENTRY_BYTES_PAPER
    ent_of_st = [np.flatnonzero(ent_st == s) for s in range(S)]
    # latency_model="edge": the embedding-derived latencies enter here
    # (inside up_term / dn_term / lat_o, same draws as the numpy
    # backend), so the jitted sweeps need no edge-vs-iid branch at all
    with TraceAnnotation("fd.engine.draws", entries=E):
        par_lat, origin_lat = _entry_latencies(sts, ent_st, p)
        draws = _precompute_draws(ent_origin, seeds, n, p, algorithm,
                                  sts[0].fw_strategy, lifetime_mean_s,
                                  independent, par_lat, origin_lat)
    out = _empty_out(E, k)
    out["jax_compile_s"] = 0.0
    out["jax_traces"] = 0
    fp64 = precision == "f64"
    use_pallas = select_pallas(use_pallas, precision)
    if fp64:
        def cast(a):
            return a
    else:
        red_dt = np_dtype(precision)

        def cast(a):
            return np.asarray(a, red_dt)
    # f64 needs the x64 flag for "same expression == same bits"; the
    # reduced modes must NOT enable it — the default f32 lattice is
    # exactly what keeps their int/float literals narrow
    x64 = jax.enable_x64 if fp64 else contextlib.nullcontext
    n_dev = jax.local_device_count() if shard else 1
    if n_dev == 1:
        shard = False

    def _timed(fn, *args, **kw):
        """A jitted sweep, its upload included, inside its span."""
        h2d = sum(a.nbytes for a in args if isinstance(a, np.ndarray))
        with TraceAnnotation("fd.engine.sweep", rows=len(args[0]),
                             h2d_bytes=h2d) as span:
            res, traced = _compiled(out, fn, *args, **kw)
            span.set_metadata(traced=int(traced))
        return res

    # ---- CN / CN*: arrival sweep on device, baseline math shared --------
    if algorithm in ("cn", "cn_star"):
        out["m_fw"][:] = np.array([st.m_basic for st in sts],
                                  np.int64)[ent_st]
        t_ex_done = np.full((E, n), np.inf)
        with x64():
            for si, st in enumerate(sts):
                es = ent_of_st[si]
                es_run, full = _pad_group(es, E, 1)
                m = len(es)
                with TraceAnnotation("fd.engine.stage", entries=m,
                                     rows=len(es_run)):
                    sl = plan.depth_slices(st)
                    levels, _, _ = _device_slices(sl)
                    te = cast(draws.t_exec if full
                              else draws.t_exec[es_run])
                    dn = cast(draws.dn_term if full
                              else draws.dn_term[es_run])
                ted = _timed(_cn_sweep, te, dn, levels)
                with TraceAnnotation("fd.engine.copy_back") as span:
                    copied: list = []
                    for d, lv in enumerate(sl.levels):
                        t_ex_done[np.ix_(es, lv["vv"])] = \
                            _to_host(ted[d], copied)[:m]
                    span.set_metadata(transfers=len(copied),
                                      d2h_bytes=sum(copied))
        with TraceAnnotation("fd.engine.epilogue"):
            _cn_entries(out, draws, sts, ent_st, ent_origin, t_ex_done, p,
                        algorithm)
        return out

    # ---- FD: jitted forward + merge sweeps per origin -------------------
    # Only what the epilogue reads leaves the device: the packed send,
    # arrival and liveness rows, the origin's merged list, and the lists
    # of the urgent children the origin accepts (``_urgent_rows``).
    with_reroute = churn and dynamic
    send_t = np.full((E, n), np.inf)
    arr_t = np.full((E, n), np.inf)      # list arrival at the parent
    valid = np.zeros((E, n), bool) if churn else None
    org_v = np.full((E, k), -np.inf)     # the origin's merged list
    org_o = np.full((E, k), -1, np.int32)
    t_merge_done = np.empty(E)
    hop_term = p.latency_mean_s + list_bytes / p.bw_mean_Bps
    with x64():
        for si, st in enumerate(sts):
            es = ent_of_st[si]
            m = len(es)
            es_run, full = _pad_group(es, E, n_dev)
            with_st1 = st.fw_strategy != "basic"

            def _take(a):
                return a if full else a[es_run]
            with TraceAnnotation("fd.engine.stage", entries=m,
                                 rows=len(es_run)):
                sl = plan.depth_slices(st, reroute=with_reroute)
                levels, els, rr = _device_slices(sl)
                tqf = lam = cast(np.zeros(0))
                if with_st1:
                    tqf = cast(np.where(st.depth >= 0,
                                        st.depth * p.t_qsnd_s, np.inf))
                    lam = cast(_take(draws.lam))
                death = cast(_take(draws.death)) if churn else cast(
                    np.zeros(0))
                scores = cast(_take(draws.scores))
                t_exec = cast(_take(draws.t_exec))
                up_term = cast(_take(draws.up_term))
                dn_term = cast(_take(draws.dn_term))
                wt = cast(wait_time(st.ttl_rem, p))
            if shard:
                fd = _sharded_fd_sweep(n_dev, k, use_pallas,
                                       with_st1, churn, with_reroute)
                kw = {}
            else:
                fd = _fd_sweep()
                kw = dict(k=k, use_pallas=use_pallas,
                          with_st1=with_st1, with_churn=churn,
                          with_reroute=with_reroute)
            send_d, arr_d, alive_d, ov_d, oo_d, skip, mv_d, mo_d = _timed(
                fd, scores, t_exec, up_term, dn_term, death, wt, tqf, lam,
                levels, els, rr if with_reroute else None, **kw)
            with TraceAnnotation("fd.engine.copy_back") as span:
                copied: list = []
                order, pos_of = _level_order(sl)
                send_t[np.ix_(es, order)] = _to_host(send_d, copied)[:m]
                arr_t[np.ix_(es, order[1:])] = _to_host(arr_d, copied)[:m]
                if churn:
                    valid[np.ix_(es, order)] = _to_host(alive_d,
                                                        copied)[:m]
                org_v[es] = _to_host(ov_d, copied)[:m]
                org_o[es] = _to_host(oo_d, copied)[:m]
                out["m_fw"][es] = (
                    st.fw_static + sl.n_els
                    - np.asarray(_to_host(skip, copied), np.int64)[:m]
                    if with_st1 else st.m_basic)
                t_merge_done[es] = send_t[es, ent_origin[es]] + p.merge_s
                n_urgent = 0
                if dynamic:
                    ue, uc = _urgent_pass(out, st, es, ent_origin, send_t,
                                          arr_t, valid, t_merge_done,
                                          hop_term, list_bytes)
                    n_urgent = len(uc)
                    # called with no rows too, so that warming a sweep
                    # compiles its gather
                    ei = np.searchsorted(es, ue)
                    cv, co = _fetch_rows(out, mv_d, mo_d, st.depth[uc],
                                         pos_of[uc], ei, copied)
                    _accept_urgent_origin(org_v, org_o, ue, cv, co, k)
                span.set_metadata(transfers=len(copied),
                                  d2h_bytes=sum(copied),
                                  urgent_rows=n_urgent)

    with TraceAnnotation("fd.engine.epilogue"):
        # every reached peer that is still alive at its send time sends its
        # list exactly once (without churn that is everyone but the origin)
        if churn:
            for si, st in enumerate(sts):
                es = ent_of_st[si]
                n_alive = valid[np.ix_(es, st.idx)].sum(axis=1)
                out["m_bw"][es] += n_alive - 1        # origin never dies
                out["b_bw"][es] += (n_alive - 1) * list_bytes
        else:
            n_reached_arr = np.array([len(st.idx) for st in sts], np.int64)
            out["m_bw"] += n_reached_arr[ent_st] - 1
            out["b_bw"] += (n_reached_arr[ent_st] - 1) * list_bytes

        # ---- §4.2 reroute accounting: one message per accepted list -----
        if with_reroute:
            for si, st in enumerate(sts):
                es = ent_of_st[si]
                cnt = _reroute_counts(st, valid[es])
                out["m_bw"][es] += cnt
                out["b_bw"][es] += cnt * list_bytes

        # ground truth from the scores AS THE SWEEP SAW THEM (cast once,
        # compared in f64 — the upcast is exact): reduced-precision runs
        # must value-match the retrieval epilogue against cast scores, and
        # in f64 this is the identical array
        with TraceAnnotation("fd.engine.truth", entries=E):
            truth_scores = (draws.scores if fp64
                            else cast(draws.scores).astype(np.float64))
            top_true_all = _true_topk_by_origin(truth_scores, sts,
                                                ent_of_st, k)
            if fp64:
                # a device that emulates float64 (TPU: about 49
                # significant bits) rounds the scores on upload, and the
                # sweep returns them so rounded; round the truth the same
                # way so the epilogue's exact value matching sees what
                # the sweep saw (a no-op where float64 is native)
                with x64():
                    top_true_all = np.asarray(jax.device_put(top_true_all))
        out["values"] = org_v
        out["owners"] = org_o.astype(np.int64)
        with TraceAnnotation("fd.engine.retrieval", entries=E):
            retrieval = (_retrieval_exact if draws.exact
                         else _retrieval_shared)
            retrieval(out, draws, t_merge_done, org_v, org_o,
                      top_true_all, p, replicas)
    return out


def _urgent_pass(out: dict, st, es: np.ndarray, ent_origin: np.ndarray,
                 send_t: np.ndarray, arr_t: np.ndarray,
                 valid: Optional[np.ndarray], t_merge_done: np.ndarray,
                 hop_term: float, list_bytes: int):
    """Urgent lists (§4.1) of one origin's entries ``es``: count the
    messages of every late child into ``out`` and return the (entry,
    child) pairs whose lists the origin accepts, entry by entry in
    child order — the late-arrival post-pass on the copied send and
    arrival times."""
    none = np.zeros(0, np.int64)
    ch = st.kid_sorted
    if len(ch) == 0:
        return none, none
    pr = st.parent[ch]
    a = arr_t[np.ix_(es, ch)]
    late = a > send_t[np.ix_(es, pr)]
    if valid is not None:
        # a dead child never went urgent; a dead parent's children
        # reroute (counted in the epilogue) instead
        late &= valid[np.ix_(es, ch)] & valid[np.ix_(es, pr)]
    if not late.any():
        return none, none
    d_par = st.depth[pr]
    ei, ci = np.nonzero(late)
    etas = a[ei, ci] + d_par[ci] * hop_term
    out["m_bw"][es] += (late * d_par[None, :]).sum(axis=1)
    out["b_bw"][es] += (late * (d_par[None, :] * list_bytes)).sum(axis=1)
    ue, uc = es[ei], ch[ci]
    ok = _urgent_accepted(ue, uc, etas, ent_origin, t_merge_done, valid)
    return ue[ok], uc[ok]


def _fetch_rows(out: dict, m_v, m_o, lvl, pos, row, copied: list):
    """Copy the lists at (level ``lvl``, position ``pos``, entry row
    ``row``) of one sweep's device-resident outputs to the host, in
    fixed chunks of ``URGENT_CHUNK`` rows through ``_urgent_rows``.
    Runs the gather once even for no rows (its result then stays on the
    device).  Returns float64 values and int32 owners."""
    k = m_v[0].shape[-1]
    vals, owns = [np.empty((0, k))], [np.empty((0, k), np.int32)]
    for c0 in range(0, max(len(row), 1), URGENT_CHUNK):
        part = slice(c0, c0 + URGENT_CHUNK)
        got = len(row[part])
        pad = (0, URGENT_CHUNK - got)
        (v, o), _ = _compiled(
            out, _urgent_rows, m_v, m_o,
            np.pad(lvl[part], pad, constant_values=-1).astype(np.int32),
            np.pad(pos[part], pad).astype(np.int32),
            np.pad(row[part], pad).astype(np.int32))
        if got:
            vals.append(_to_host(v, copied)[:got])
            owns.append(_to_host(o, copied)[:got])
    return (np.concatenate(vals).astype(np.float64, copy=False),
            np.concatenate(owns))
