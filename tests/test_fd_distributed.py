"""FD distributed top-k vs CN / CN* and the global oracle — on 8 fake
devices in a subprocess (tests in-process must see 1 device)."""

import pytest


def test_fd_all_schedules_and_baselines(devices8):
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.fd import fd_topk, fd_topk_gather
from repro.jaxcompat import make_mesh
mesh = make_mesh((8,), ("model",))
scores = jax.random.normal(jax.random.PRNGKey(3), (2, 1024))
rv, ri = jax.lax.top_k(scores, 20)
for sched in ("halving", "doubling", "ring"):
    fv, fi = fd_topk(scores, 20, mesh, "model", schedule=sched)
    np.testing.assert_allclose(np.asarray(fv), np.asarray(rv), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(ri))
for alg in ("cn", "cn_star"):
    fv, fi = fd_topk(scores, 20, mesh, "model", algorithm=alg)
    np.testing.assert_allclose(np.asarray(fv), np.asarray(rv), atol=1e-6)
# phase-4 gather: only winning rows cross
s1 = jax.random.normal(jax.random.PRNGKey(5), (512,))
rows = jax.random.normal(jax.random.PRNGKey(6), (512, 16))
vals, idx, got = fd_topk_gather(s1, rows, 4, mesh, "model")
ref_v, ref_i = jax.lax.top_k(s1, 4)
np.testing.assert_allclose(np.asarray(vals), np.asarray(ref_v), atol=1e-6)
np.testing.assert_allclose(np.asarray(got), np.asarray(rows)[np.asarray(ref_i)],
                           atol=1e-6)
print("FD_OK")
""")
    assert "FD_OK" in out


@pytest.mark.parametrize("sched", ["halving", "doubling", "ring"])
def test_fd_schedules_break_ties_like_topk_ref(devices8, sched):
    """Tied scores: every schedule, on every device, returns lax.top_k's
    indices (lowest index first among equal scores), and the gathered
    rows are the rows of those indices."""
    out = devices8(f"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.fd import fd_topk, fd_topk_gather
from repro.jaxcompat import make_mesh
mesh = make_mesh((8,), ("model",))
scores = jnp.round(jax.random.normal(jax.random.PRNGKey(3), (2, 4096)) * 2)
rv, ri = jax.lax.top_k(scores, 20)
assert len(np.unique(np.asarray(rv))) < 20          # ties inside the top k
fv, fi = fd_topk(scores, 20, mesh, "model", schedule="{sched}")
for shard in fi.addressable_shards:
    np.testing.assert_array_equal(np.asarray(shard.data), np.asarray(ri))
np.testing.assert_array_equal(np.asarray(fv), np.asarray(rv))
rows = jax.random.normal(jax.random.PRNGKey(6), (4096, 16))
_, gi, got = fd_topk_gather(scores[0], rows, 20, mesh, "model",
                            schedule="{sched}")
np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri[0]))
np.testing.assert_array_equal(np.asarray(got), np.asarray(rows)[np.asarray(ri[0])])
print("TIES_OK")
""")
    assert "TIES_OK" in out


def test_fd_with_batch_axes(devices8):
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.fd import fd_topk
from repro.jaxcompat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
scores = jax.random.normal(jax.random.PRNGKey(0), (4, 512))
fv, fi = fd_topk(scores, 8, mesh, "model", batch_axes=("data",))
rv, ri = jax.lax.top_k(scores, 8)
np.testing.assert_allclose(np.asarray(fv), np.asarray(rv), atol=1e-6)
print("BATCH_OK")
""")
    assert "BATCH_OK" in out


def test_fd_sparse_allreduce(devices8):
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np
from repro.optim.compress import (CompressState, compress_init,
                                  fd_sparse_allreduce, inflate_k)
from repro.jaxcompat import make_mesh
mesh = make_mesh((8,), ("pod",))
# per-pod distinct gradients; sparse mean must converge to dense mean
# with error feedback over rounds
g = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 32))}
ef = compress_init(g)
g_hat, ef2 = fd_sparse_allreduce(g, ef, mesh, axis="pod", k_frac=0.05)
assert g_hat["w"].shape == (64, 32)
# conservation: selected + residual == accumulated signal
dense_mean = g["w"]  # identical on every pod -> mean == g
err0 = float(jnp.abs(g_hat["w"] - dense_mean).mean())
# second round sends the residual (error feedback drains)
zero = {"w": jnp.zeros_like(g["w"])}
g_hat2, ef3 = fd_sparse_allreduce(zero, ef2, mesh, axis="pod", k_frac=0.05)
total = g_hat["w"] + g_hat2["w"]
err1 = float(jnp.abs(total - dense_mean).mean())
assert err1 < err0, (err0, err1)
assert inflate_k(20, 0.2) == 25    # Lemma 4: k/(1-P)
print("COMPRESS_OK", err0, err1)
""")
    assert "COMPRESS_OK" in out


def test_serve_step_fd_equals_cn(devices8):
    """The full serving path: FD sampling == CN sampling (same winners)."""
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config, smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.runtime.steps import make_serve_step
cfg = smoke_config(get_config("qwen2-0.5b"))
mesh = make_host_mesh(model=4)
from repro.jaxcompat import use_mesh
ctx = use_mesh(mesh); ctx.__enter__()
params = M.init_params(jax.random.PRNGKey(0), cfg, max_seq=64)
state = M.init_decode_state(cfg, batch=2, s_max=32,
                            cache_dtype=jnp.float32)
tok = jnp.ones((2, 1), jnp.int32)
rng = jax.random.PRNGKey(7)
outs = {}
for alg in ("fd", "cn", "cn_star"):
    step = jax.jit(make_serve_step(cfg, mesh, k=8, algorithm=alg,
                                   batch_axes=("data",)))
    t, _ = step(params, state, tok, rng)
    outs[alg] = np.asarray(t)
np.testing.assert_array_equal(outs["fd"], outs["cn"])
np.testing.assert_array_equal(outs["fd"], outs["cn_star"])
print("SERVE_OK", outs["fd"].ravel().tolist())
""", timeout=600)
    assert "SERVE_OK" in out


def test_fd_gather_batched_queries(devices8):
    """A batch of queries over ONE sharded table: every schedule, plus
    batch sharding over the data axis (phase-4 masked psum per query)."""
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.fd import fd_topk, fd_topk_gather
from repro.jaxcompat import make_mesh
mesh = make_mesh((8,), ("model",))
s = jax.random.normal(jax.random.PRNGKey(5), (4, 512))
rows = jax.random.normal(jax.random.PRNGKey(6), (512, 16))
rv, ri = jax.lax.top_k(s, 4)
for sched in ("halving", "doubling", "ring"):
    vals, idx, got = fd_topk_gather(s, rows, 4, mesh, "model",
                                    schedule=sched)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rv), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(rows)[np.asarray(ri)], atol=1e-6)
mesh2 = make_mesh((2, 4), ("data", "model"))
s2 = jax.random.normal(jax.random.PRNGKey(7), (4, 512))
rows2 = jax.random.normal(jax.random.PRNGKey(8), (512, 8))
rv2, ri2 = jax.lax.top_k(s2, 6)
vals, idx, got = fd_topk_gather(s2, rows2, 6, mesh2, "model",
                                batch_axes=("data",))
np.testing.assert_allclose(np.asarray(vals), np.asarray(rv2), atol=1e-6)
np.testing.assert_allclose(np.asarray(got),
                           np.asarray(rows2)[np.asarray(ri2)], atol=1e-6)
for sched in ("halving", "doubling", "ring"):
    fv, fi = fd_topk(s2, 6, mesh2, "model", schedule=sched,
                     batch_axes=("data",))
    np.testing.assert_allclose(np.asarray(fv), np.asarray(rv2), atol=1e-6)
print("GATHER_BATCH_OK")
""")
    assert "GATHER_BATCH_OK" in out
