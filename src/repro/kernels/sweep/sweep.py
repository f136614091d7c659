"""Pallas kernel for the per-depth wait-propagation hot loop.

``wait_pallas`` computes the Appendix-A send-time rule
``min(max(own_ready, all_in), max(deadline, own_ready))`` in one
elementwise pass over (entries, level width); the churn variant also
emits the liveness-masked send time (``inf`` for a peer dead at its
send time), so the mask costs no extra memory round trip.  The grid
tiles the entry axis in blocks of up to ``_ROWS`` rows and the level
axis in blocks of up to ``_COLS`` lanes (full extent when an axis is
smaller, ``pl.cdiv`` otherwise).

The kernel preserves the input dtype (f32 / bf16, and f64 in interpret
mode) and groups its float ops exactly as the jnp oracle in ``ref.py``,
so the f64 CPU path keeps the repo's bit-parity contract.  The forward
flood's gather+add (``ref.arrivals_ref``) has no kernel: it gathers
along lanes with a data-dependent index, which Mosaic does not lower,
and XLA fuses the jnp expression.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import check_mosaic_dtype, resolve_interpret

_ROWS = 64
_COLS = 2048


def _load(ref):
    # min / max / compare only select, so bf16 runs in f32 (exact both
    # ways) — v5e's VPU has no bf16 compare
    x = ref[...]
    return x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x


def _send_time(r_ref, a_ref, d_ref):
    own = _load(r_ref)
    return jnp.minimum(jnp.maximum(own, _load(a_ref)),
                       jnp.maximum(_load(d_ref), own))


def _wait_kernel(r_ref, a_ref, d_ref, o_ref):
    o_ref[...] = _send_time(r_ref, a_ref, d_ref).astype(o_ref.dtype)


def _wait_churn_kernel(r_ref, a_ref, d_ref, death_ref, s_ref, snd_ref):
    s = _send_time(r_ref, a_ref, d_ref)
    s_ref[...] = s.astype(s_ref.dtype)
    # dead at send time -> an arrival that can never release a parent
    snd_ref[...] = jnp.where(_load(death_ref) >= s, s,
                             jnp.inf).astype(snd_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wait_pallas(own_ready, all_in, deadline, death=None, *,
                interpret=None):
    """Appendix-A send times as a Pallas kernel (optionally churned).

    All operands (E, L), dtype preserved.  Without ``death`` returns
    the raw send time ``s``; with ``death`` returns ``(s, send)`` where
    ``send`` is ``s`` masked to ``inf`` for peers dead at their send
    time — the exact fill the churn sweep commits.  ``interpret=None``
    interprets off-TPU and compiles on TPU; a compiled kernel refuses
    float64.
    """
    interpret = resolve_interpret(interpret)
    E, L = own_ready.shape
    dt = jnp.result_type(own_ready, all_in, deadline)
    check_mosaic_dtype("wait_pallas", dt, interpret)
    te, tl = min(E, _ROWS), min(L, _COLS)
    spec = pl.BlockSpec((te, tl), lambda i, j: (i, j))
    call = functools.partial(
        pl.pallas_call, grid=(pl.cdiv(E, te), pl.cdiv(L, tl)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret)
    out = jax.ShapeDtypeStruct((E, L), dt)
    args = (own_ready.astype(dt), all_in.astype(dt), deadline.astype(dt))
    if death is None:
        return call(_wait_kernel, in_specs=[spec] * 3, out_specs=spec,
                    out_shape=out)(*args)
    s, send = call(_wait_churn_kernel, in_specs=[spec] * 4,
                   out_specs=[spec, spec],
                   out_shape=[out, out])(*args, death.astype(dt))
    return s, send
