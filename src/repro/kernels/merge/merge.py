"""Score-list merge Pallas TPU kernel (Merge-and-Backward phase).

Merges two descending k-lists into the top-k of their union with a
bitonic merge network: with both lists padded to K = 2^ceil(log2 k),
``max(a_j, b_{K-1-j})`` holds the top-K multiset of the union as a
bitonic sequence, and log2(K) half-cleaner stages sort it descending —
the same network, compare for compare, as the fused jnp merge
``repro.engine.sim_jax._merge_desc``, so both paths give the same bits.

Layout: lists are stored position-major.  The wrapper turns the
(batch, k) operands into (k, batch / 128, 128) arrays, so each list
position is one (rows, 128) slab and every compare-exchange of the
network is a full-tile elementwise min/max/select between two slabs —
no lane shuffles, no gathers (Mosaic lowers neither a lane reverse nor
a dynamic lane gather).  The grid walks the batch in blocks of
``_ROWS`` x 128 lists.  Validated against ref.merge_ref in interpret
mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import check_mosaic_dtype, resolve_interpret

NEG_INF = float("-inf")
_LANES = 128
_ROWS = 32          # sublane rows per block: 32 x 128 lists per grid step


def _next_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def _merge_kernel(va_ref, ia_ref, vb_ref, ib_ref, *refs,
                  k: int, K: int, dt, masked: bool):
    # the network only compares and selects, so bf16 lists can run it
    # in f32 (exact both ways) — v5e's VPU has no bf16 compare
    ct = jnp.float32 if dt == jnp.bfloat16 else dt
    if masked:
        ma_ref, mb_ref, vo_ref, io_ref = refs
    else:
        vo_ref, io_ref = refs
    shape = va_ref.shape[1:]
    pad_v = jnp.full(shape, NEG_INF, ct)
    pad_i = jnp.full(shape, -1, jnp.int32)
    a_v = [va_ref[j].astype(ct) for j in range(k)] + [pad_v] * (K - k)
    a_i = [ia_ref[j] for j in range(k)] + [pad_i] * (K - k)
    b_v = [vb_ref[j].astype(ct) for j in range(k)] + [pad_v] * (K - k)
    b_i = [ib_ref[j] for j in range(k)] + [pad_i] * (K - k)
    if masked:
        # validity masking in VMEM: a dead peer's list becomes -inf rows
        # (it can never beat a live score) — pure select, no control flow
        live_a = ma_ref[...] != 0
        live_b = mb_ref[...] != 0
        a_v = [jnp.where(live_a, x, NEG_INF) for x in a_v]
        b_v = [jnp.where(live_b, x, NEG_INF) for x in b_v]
    v, o = [], []
    for j in range(K):
        take = a_v[j] >= b_v[K - 1 - j]
        v.append(jnp.where(take, a_v[j], b_v[K - 1 - j]))
        o.append(jnp.where(take, a_i[j], b_i[K - 1 - j]))
    s = K // 2
    while s >= 1:
        for lo in range(K):
            if lo & s:
                continue
            hi = lo + s
            # a true compare-exchange: equal scores stay put, so a tie
            # never copies one owner over the other
            swap = v[lo] < v[hi]
            v[lo], v[hi] = (jnp.where(swap, v[hi], v[lo]),
                            jnp.where(swap, v[lo], v[hi]))
            o[lo], o[hi] = (jnp.where(swap, o[hi], o[lo]),
                            jnp.where(swap, o[lo], o[hi]))
        s //= 2
    for j in range(k):
        vo_ref[j] = v[j].astype(dt)
        io_ref[j] = o[j]


def _position_major(x, b: int, bp: int):
    """(..., k) -> (k, bp / 128, 128): list positions lead, the batch
    (zero-padded to ``bp``) fills the (sublane, lane) tile."""
    k = x.shape[-1]
    x = x.reshape((b, k)).T
    if bp != b:
        x = jnp.pad(x, ((0, 0), (0, bp - b)))
    return x.reshape((k, bp // _LANES, _LANES))


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_pallas(vals_a, idx_a, vals_b, idx_b, *, interpret=None,
                 valid_a=None, valid_b=None):
    """Merge two descending k-lists -> top-k of the union (descending).

    f32 and bf16 lists merge in their own dtype; non-float / f16 inputs
    keep the historical f32 compute dtype.  float64 lists run only in
    interpret mode (the CPU path): a compiled kernel refuses them.
    ``interpret=None`` interprets off-TPU and compiles on TPU.

    ``valid_a`` / ``valid_b``: optional boolean row masks over the
    leading axes (churned-out peers).  Masking happens inside the kernel
    on the VMEM-resident block — an invalid list's values become -inf
    before the network runs, identical to pre-masking the HBM input but
    without materializing a masked copy.
    """
    interpret = resolve_interpret(interpret)
    lead = vals_a.shape[:-1]
    k = vals_a.shape[-1]
    dt = jnp.result_type(vals_a, vals_b)
    if not jnp.issubdtype(dt, jnp.floating) or dt == jnp.float16:
        dt = jnp.promote_types(dt, jnp.float32)
    check_mosaic_dtype("merge_pallas", dt, interpret)
    b = 1
    for d in lead:
        b *= d
    bp = -(-b // _LANES) * _LANES
    rows = bp // _LANES
    tb = min(rows, _ROWS)
    args = [_position_major(x, b, bp)
            for x in (vals_a.astype(dt), idx_a.astype(jnp.int32),
                      vals_b.astype(dt), idx_b.astype(jnp.int32))]
    spec = pl.BlockSpec((k, tb, _LANES), lambda i: (0, i, 0))
    in_specs = [spec] * 4
    masked = valid_a is not None or valid_b is not None
    if masked:
        for valid in (valid_a, valid_b):
            m = (jnp.ones((b,), jnp.int32) if valid is None
                 else valid.reshape((b,)).astype(jnp.int32))
            args.append(jnp.pad(m, (0, bp - b)).reshape((rows, _LANES)))
        in_specs += [pl.BlockSpec((tb, _LANES), lambda i: (i, 0))] * 2
    kern = functools.partial(_merge_kernel, k=k, K=_next_pow2(k), dt=dt,
                             masked=masked)
    vo, io = pl.pallas_call(
        kern,
        grid=(pl.cdiv(rows, tb),),
        in_specs=in_specs,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((k, rows, _LANES), dt),
                   jax.ShapeDtypeStruct((k, rows, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)

    def back(x):
        return x.reshape((k, bp))[:, :b].T.reshape(lead + (k,))
    return back(vo), back(io)
