"""Platform selection: what runs compiled, interpreted or on jnp, where.

These run on the CPU; the TPU platform is monkeypatched where a test
needs to see the chip's branch.  Together they pin the rules of
``repro.kernels.platform``: nothing on the chip interprets a Pallas
kernel, float64 never enters a Mosaic kernel, and the sweep's kernel
path follows the platform and the precision.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import QuerySpec, SimEngine
from repro.engine import sim_jax
from repro.kernels import platform
from repro.p2psim import SimParams, barabasi_albert


@pytest.mark.parametrize("tpu,expected", [(False, True), (True, False)])
def test_interpret_none_resolves_off_tpu_only(monkeypatch, tpu, expected):
    monkeypatch.setattr(platform, "on_tpu", lambda: tpu)
    assert platform.resolve_interpret(None) is expected
    # an explicit choice is kept on either platform
    assert platform.resolve_interpret(True) is True
    assert platform.resolve_interpret(False) is False


@pytest.mark.parametrize("tpu,use_pallas,precision,expected", [
    (True, None, "f64", False),     # the default is the fused jnp path
    (True, None, "f32", False),     # on every platform and precision
    (True, None, "bf16", False),
    (False, None, "f32", False),
    (True, True, "f32", True),      # forced on TPU: compiled kernels
    (True, True, "bf16", True),
    (False, True, "f64", True),     # forced off-TPU: the interpreter
    (True, False, "f32", False),
])
def test_select_pallas(monkeypatch, tpu, use_pallas, precision, expected):
    monkeypatch.setattr(sim_jax, "on_tpu", lambda: tpu)
    assert sim_jax.select_pallas(use_pallas, precision) is expected


def test_use_pallas_f64_on_tpu_raises(monkeypatch):
    """An explicit use_pallas=True at f64 on TPU is refused before any
    sweep runs — it neither interprets nor reaches Mosaic."""
    monkeypatch.setattr(sim_jax, "on_tpu", lambda: True)
    eng = SimEngine(barabasi_albert(48, m=2, seed=1), SimParams(seed=2),
                    backend="jax", use_pallas=True)
    with pytest.raises(ValueError, match="f64"):
        eng.run(QuerySpec(origins=(0,)), "fd-dynamic")
    with pytest.raises(ValueError, match="f64"):
        sim_jax.select_pallas(True, "f64")


def test_fd_topk_shard_leaves_interpret_to_the_platform(monkeypatch):
    """``fd_topk_shard``'s local top-k passes ``interpret=None`` down to
    the kernel, which resolves it from the platform — on the chip the
    kernel compiles; it used to run in the interpreter."""
    from repro.core import fd
    from repro.jaxcompat import make_mesh
    from repro.kernels.topk import ops

    seen = []
    real = ops.topk_pallas

    def spy(*args, **kwargs):
        seen.append(kwargs.get("interpret"))
        return real(*args, **kwargs)

    monkeypatch.setattr(ops, "topk_pallas", spy)
    mesh = make_mesh((1,), ("model",))
    x = jax.random.normal(jax.random.PRNGKey(0), (512,))
    vals, idx = fd.fd_topk(x, 5, mesh, use_pallas=True)
    ref_v, ref_i = jax.lax.top_k(x, 5)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_i))
    assert seen == [None]
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    assert platform.resolve_interpret(seen[0]) is False


def test_enable_x64_is_thread_local():
    """The f64 sweep's ``jax.enable_x64()`` scope does not leak x64 into
    another thread tracing at the same time."""
    inside, seen = threading.Event(), {}
    release = threading.Event()

    def x64_thread():
        with jax.enable_x64():
            seen["x64"] = jnp.asarray(1.0).dtype
            inside.set()
            release.wait(30)

    t = threading.Thread(target=x64_thread)
    t.start()
    try:
        assert inside.wait(30)
        seen["other"] = jnp.asarray(1.0).dtype
    finally:
        release.set()
        t.join(30)
    assert seen == {"x64": jnp.float64, "other": jnp.float32}


def test_compile_cache_honours_env(monkeypatch):
    from repro import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/where")
    assert compile_cache.enable_compile_cache() == "/some/where"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    from repro import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = compile_cache.checkout_dir()
    assert (root / "src" / "repro" / "compile_cache.py").is_file()
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_off_outside_a_checkout(monkeypatch, tmp_path):
    """A ``repro`` installed outside a checkout (site-packages) writes
    no cache next to the install: without the variable the cache stays
    off."""
    from repro import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache, "_PKG",
                        tmp_path / "site-packages" / "repro")
    assert compile_cache.checkout_dir() is None
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
