"""Every Pallas kernel on the TPU path compiles for a described v5e chip.

The chip's own compiler (libtpu) is installed even where no TPU is
attached: ``get_topology_desc`` describes a ``v5e:2x2`` host and
``jit(...).lower(...).compile()`` runs Mosaic exactly as on the chip.
Interpret-mode tests cannot see tiling or VMEM refusals; these can.
Each compile is at a real width — k=20 lists over a 100k-peer plan's
entries x level width, 152064-wide score rows (a Qwen2 vocabulary) —
with ``interpret=False``, and asserts that the kernel is in the
compiled program (``tpu_custom_call``).

The topology is described inside a module fixture (never at import:
one process at a time may load libtpu), and the persistent compile
cache is off around the compiles (an AOT entry cannot be read back
without a chip).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.merge.merge import merge_pallas
from repro.kernels.sweep.sweep import wait_pallas
from repro.kernels.topk.topk import topk_pallas

E, LEVEL = 64, 5000          # entries x widest level of a 100k-peer ba plan
K = 32                       # k=20 lists, padded to a power of two


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # noqa: BLE001 — any refusal skips
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _lists(chip, dtype, lead=(E, LEVEL), k=K):
    return (jax.ShapeDtypeStruct(lead + (k,), dtype, sharding=chip),
            jax.ShapeDtypeStruct(lead + (k,), jnp.int32, sharding=chip))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_merge_compiles(one_chip, dtype):
    va, ia = _lists(one_chip, dtype)
    _compile(functools.partial(merge_pallas, interpret=False),
             va, ia, va, ia)


def test_merge_masked_compiles(one_chip):
    va, ia = _lists(one_chip, jnp.float32)
    mask = jax.ShapeDtypeStruct((E, LEVEL), jnp.bool_, sharding=one_chip)
    _compile(lambda a, b, c, d, m1, m2: merge_pallas(
        a, b, c, d, valid_a=m1, valid_b=m2, interpret=False),
        va, ia, va, ia, mask, mask)


@pytest.mark.parametrize("lead", [(1,), (4, 3), (4096,)])
def test_merge_compiles_unpadded_k(one_chip, lead):
    """k=20 straight (no pow2 padding) over a single list, a batch below
    one (8, 128) tile, and one spanning full tiles."""
    va, ia = _lists(one_chip, jnp.float32, lead=lead, k=20)
    _compile(functools.partial(merge_pallas, interpret=False),
             va, ia, va, ia)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("churn", [False, True])
def test_wait_compiles(one_chip, dtype, churn):
    x = jax.ShapeDtypeStruct((E, LEVEL), dtype, sharding=one_chip)
    if churn:
        _compile(lambda a, b, c, d: wait_pallas(a, b, c, d,
                                                interpret=False),
                 x, x, x, x)
    else:
        _compile(lambda a, b, c: wait_pallas(a, b, c, interpret=False),
                 x, x, x)


@pytest.mark.parametrize("shape", [(8, 152064), (1, 1 << 20), (13, 4097)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_compiles(one_chip, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    _compile(lambda s: topk_pallas(s, 20, interpret=False), x)


def test_compiled_kernels_refuse_f64(one_chip):
    """float64 never enters a Mosaic kernel: the wrappers refuse it
    before lowering, instead of interpreting or failing in Mosaic."""
    v64 = jax.ShapeDtypeStruct((8, K), jnp.float64, sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((8, K), jnp.int32, sharding=one_chip)
    with jax.enable_x64():
        with pytest.raises(ValueError, match="float64"):
            jax.jit(functools.partial(merge_pallas, interpret=False)).lower(
                v64, i32, v64, i32)
        with pytest.raises(ValueError, match="float64"):
            jax.jit(lambda a: wait_pallas(a, a, a, interpret=False)).lower(
                v64)
