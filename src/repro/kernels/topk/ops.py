"""jit'd public wrapper for the local top-k kernel.

``local_topk`` dispatches to the Pallas kernel (interpret mode off-TPU,
compiled on TPU: ``interpret=None``) or the XLA reference, and always returns f32 values +
int32 global indices in descending order.
"""
from __future__ import annotations

import jax

from repro.kernels.topk.ref import topk_ref
from repro.kernels.topk.topk import topk_pallas


def local_topk(scores: jax.Array, k: int, *, index_offset: int = 0,
               use_pallas: bool = False, tile_n: int = 1024,
               interpret=None):
    """Top-k (vals, global idx) of ``scores`` along the last axis.

    The paper's Local Query Execution: score local items, keep the k best
    couples.  ``index_offset`` turns local positions into global addresses
    (shard_offset = axis_index * shard_size).
    """
    if use_pallas:
        return topk_pallas(scores, k, tile_n=tile_n,
                           index_offset=index_offset, interpret=interpret)
    return topk_ref(scores, k, index_offset=index_offset)
