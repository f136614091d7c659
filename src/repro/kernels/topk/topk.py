"""Blocked local top-k Pallas TPU kernel.

Streams HBM->VMEM tiles of a long score vector and maintains a running
k-list (values + global indices) in VMEM scratch, exactly the paper's
local-query-execution phase with bounded memory:

    for each tile t:                       # grid dim 1 (sequential)
        cand = concat(running_k, tile)     # (k + tile_n,)
        running_k = extract_top_k(cand)    # k iterations of max/argmax/mask

Design notes (TPU mapping):
  * blocks are (rows, tile_n): up to 8 score rows per block (the whole
    batch when it is smaller) and tile_n a multiple of 128 lanes; the
    grid is (cdiv(rows), cdiv(n, tile_n)) and the ragged last tile is
    masked in-kernel, so the wrapper never pads the scores in HBM.
  * extraction uses only max / min / select over the running list and
    the tile — no sort, no gather, no lane concatenation — all
    Mosaic-lowerable vector primitives.  The running list is searched
    before the tile, which reproduces "first position of the max" over
    ``concat(running_k, tile)``.
  * the running list lives in VMEM scratch and persists across the
    sequential grid dimension; output is written on the last tile.
  * numerically the kernel works in f32 regardless of input dtype (scores
    are compared, never accumulated, so f32 is exact for bf16/f16 inputs).

Validated against ref.topk_ref in interpret mode (CPU) across shape/dtype
sweeps; see tests/test_kernels_topk.py.
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
_ROWS = 8           # score rows per block (the f32 sublane tile)


def _extract_topk(run_v, run_i, x, base, k: int):
    """k rounds of (max, first-argmax, mask) over ``run ++ x``.

    run_v / run_i: (r, k) f32 / i32 running list (descending, may hold
    -inf with index -1).  x: (r, t) f32 tile, -inf where masked; its
    column c has global index ``base + c``.  Returns the (r, k) top-k of
    the union, values descending, ties to the lower candidate position.
    """
    k_iota = jax.lax.broadcasted_iota(jnp.int32, run_v.shape, 1)
    t_iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    big = jnp.int32(x.shape[1] + k)

    def body(j, carry):
        rv, xv, ov, oi = carry
        mx = jnp.maximum(jnp.max(rv, axis=1, keepdims=True),
                         jnp.max(xv, axis=1, keepdims=True))      # (r,1)
        pos_r = jnp.min(jnp.where(rv == mx, k_iota, big), axis=1,
                        keepdims=True)
        pos_t = jnp.min(jnp.where(xv == mx, t_iota, big), axis=1,
                        keepdims=True)
        in_run = pos_r < big
        sel_r = (k_iota == pos_r) & in_run
        sel_t = (t_iota == pos_t) & ~in_run
        gi = jnp.where(in_run,
                       jnp.sum(jnp.where(sel_r, run_i, 0), axis=1,
                               keepdims=True),
                       base + pos_t)
        ov = jnp.where(k_iota == j, mx, ov)
        oi = jnp.where(k_iota == j, gi, oi)
        rv = jnp.where(sel_r, NEG_INF, rv)
        xv = jnp.where(sel_t, NEG_INF, xv)
        return rv, xv, ov, oi

    ov0 = jnp.full(run_v.shape, NEG_INF, jnp.float32)
    oi0 = jnp.full(run_v.shape, -1, jnp.int32)
    _, _, ov, oi = jax.lax.fori_loop(0, k, body, (run_v, x, ov0, oi0))
    return ov, oi


def _topk_kernel(x_ref, vals_ref, idx_ref, run_v, run_i, *,
                 k: int, tile_n: int, n_tiles: int, n_valid: int,
                 index_offset: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        run_v[...] = jnp.full(run_v.shape, NEG_INF, jnp.float32)
        run_i[...] = jnp.full(run_i.shape, -1, jnp.int32)

    x = x_ref[...].astype(jnp.float32)                     # (r, tile_n)
    local = t * tile_n + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(local < n_valid, x, NEG_INF)             # ragged tail
    rv, ri = _extract_topk(run_v[...], run_i[...], x,
                           t * tile_n + index_offset, k)
    run_v[...] = rv
    run_i[...] = ri

    @pl.when(t == n_tiles - 1)
    def _out():
        vals_ref[...] = rv
        idx_ref[...] = ri


@functools.partial(jax.jit, static_argnames=("k", "tile_n", "interpret",
                                             "index_offset"))
def topk_pallas(scores: jax.Array, k: int, *, tile_n: int = 1024,
                index_offset: int = 0, interpret=None):
    """Blocked top-k over the last axis of ``scores`` (any leading batch).

    Returns (vals f32 (..., k), idx i32 (..., k)) in descending value order.
    ``tile_n`` must be a multiple of 128; ``interpret=None`` interprets
    off-TPU and compiles on TPU.
    """
    if scores.ndim == 1:
        v, i = topk_pallas(scores[None], k, tile_n=tile_n,
                           index_offset=index_offset, interpret=interpret)
        return v[0], i[0]
    interpret = resolve_interpret(interpret)
    check_mosaic_dtype("topk_pallas", scores.dtype, interpret)
    if tile_n % _LANES:
        raise ValueError(f"tile_n={tile_n} is not a multiple of {_LANES}")
    lead = scores.shape[:-1]
    n = scores.shape[-1]
    if k > n:
        raise ValueError(f"k={k} > n={n}")
    x = scores.reshape((-1, n))
    b = x.shape[0]
    tile_n = min(tile_n, -(-n // _LANES) * _LANES)
    n_tiles = pl.cdiv(n, tile_n)
    rows = min(b, _ROWS)

    kern = functools.partial(
        _topk_kernel, k=k, tile_n=tile_n, n_tiles=n_tiles, n_valid=n,
        index_offset=index_offset)
    vals, idx = pl.pallas_call(
        kern,
        grid=(pl.cdiv(b, rows), n_tiles),
        in_specs=[pl.BlockSpec((rows, tile_n), lambda i, t: (i, t))],
        out_specs=[pl.BlockSpec((rows, k), lambda i, t: (i, 0)),
                   pl.BlockSpec((rows, k), lambda i, t: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, k), jnp.float32),
                   jax.ShapeDtypeStruct((b, k), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((rows, k), jnp.float32),
                        pltpu.VMEM((rows, k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x)
    return vals.reshape(lead + (k,)), idx.reshape(lead + (k,))
