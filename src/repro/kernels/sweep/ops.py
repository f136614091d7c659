"""Dispatch layer for the wait-propagation kernel (mirrors merge/ops.py).

``use_pallas=False`` routes to the jnp oracle (what XLA fuses best);
``use_pallas=True`` routes to the Pallas kernel — interpreted off-TPU,
compiled Mosaic on TPU (``interpret=None``).  Both paths produce the
same bits in f64 (interpret mode) and preserve f32 / bf16 dtypes.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.sweep.ref import wait_ref
from repro.kernels.sweep.sweep import wait_pallas


def wait_propagate(own_ready, all_in, deadline, *, death=None,
                   use_pallas: bool = False, interpret=None):
    """Appendix-A send times; with ``death`` also the churn-masked send.

    Returns ``s`` (E, L), or ``(s, send)`` when ``death`` is given,
    with ``send = where(death >= s, s, inf)``.
    """
    if use_pallas:
        return wait_pallas(own_ready, all_in, deadline, death,
                           interpret=interpret)
    s = wait_ref(own_ready, all_in, deadline)
    if death is None:
        return s
    return s, jnp.where(death >= s, s, jnp.inf)
