"""Platform rules shared by the Pallas kernels.

A kernel runs compiled (Mosaic) on TPU and in the Pallas interpreter
everywhere else; ``interpret=None`` means exactly that.  Nothing on the
chip ever interprets, and float64 never enters a Mosaic kernel: the TPU
has no native f64, so f64 callers stay on the fused jnp paths there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret) -> bool:
    """``None`` -> interpret off-TPU only; an explicit bool is kept."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def check_mosaic_dtype(kernel: str, dtype, interpret: bool) -> None:
    """Refuse float64 operands for a compiled (non-interpreted) kernel."""
    if not interpret and jnp.dtype(dtype) == jnp.float64:
        raise ValueError(
            f"{kernel}: float64 cannot enter a compiled Mosaic kernel "
            "(the TPU has no native f64); run f64 on the jnp path")

