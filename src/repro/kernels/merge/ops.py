"""jit'd public wrapper for the score-list merge kernel."""
from __future__ import annotations

from repro.kernels.merge.merge import merge_pallas
from repro.kernels.merge.ref import merge_ref


def merge_scorelists(vals_a, idx_a, vals_b, idx_b, *, use_pallas: bool = False,
                     interpret=None, valid_a=None, valid_b=None):
    """Merge-and-Backward: top-k of the union of two descending k-lists.

    ``valid_a`` / ``valid_b``: optional boolean row masks over the leading
    axes — an invalid (churned-out) list contributes -inf values instead
    of branching; see the churn sweep in ``repro.engine.sim_jax``.
    ``interpret=None`` interprets the kernel off-TPU only.
    """
    if use_pallas:
        return merge_pallas(vals_a, idx_a, vals_b, idx_b,
                            interpret=interpret,
                            valid_a=valid_a, valid_b=valid_b)
    return merge_ref(vals_a, idx_a, vals_b, idx_b,
                     valid_a=valid_a, valid_b=valid_b)
