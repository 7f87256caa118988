"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

KEY = jax.random.key(0)


@pytest.mark.parametrize("B,S,Hq,Hkv,D,win,dtype", [
    (2, 128, 4, 2, 64, None, jnp.float32),
    (1, 256, 4, 1, 64, None, jnp.float32),
    (2, 128, 8, 2, 128, None, jnp.float32),
    (1, 128, 4, 4, 64, 32, jnp.float32),
    (1, 128, 2, 2, 112, None, jnp.float32),  # head-dim padding path (kimi)
    (2, 128, 4, 2, 64, None, jnp.bfloat16),
])
def test_flash_attention_allclose(B, S, Hq, Hkv, D, win, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, S, Hq, D), dtype)
    k = jax.random.normal(k2, (B, S, Hkv, D), dtype)
    v = jax.random.normal(k3, (B, S, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=win)
    exp = ops.flash_attention(q, k, v, causal=True, window=win, backend="jnp")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@settings(max_examples=8, deadline=None)
@given(bq=st.sampled_from([32, 64]), bk=st.sampled_from([32, 64]),
       seed=st.integers(0, 99))
def test_flash_attention_block_invariance(bq, bk, seed):
    """Output must not depend on the BlockSpec tiling."""
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(k1, (1, 128, 2, 64), jnp.float32)
    k = jax.random.normal(k2, (1, 128, 2, 64), jnp.float32)
    v = jax.random.normal(k3, (1, 128, 2, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk)
    exp = ops.flash_attention(q, k, v, backend="jnp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 128, 4, 64, 1, 32, 32),
    (1, 64, 2, 64, 2, 16, 16),
    (1, 256, 8, 64, 1, 128, 64),
])
def test_ssd_kernel_allclose(b, s, h, p, g, n, chunk):
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    x = jax.random.normal(k1, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k2, (b, s, h))) * 0.1
    A = -jnp.exp(jax.random.normal(k3, (h,)) * 0.5)
    B = jax.random.normal(k4, (b, s, g, n)) * 0.3
    C = jax.random.normal(k1, (b, s, g, n)) * 0.3
    y_k, st_k = ops.ssd(x, dt, A, B, C, chunk)
    y_r, st_r = ref.ssd_ref(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_r), atol=1e-4,
                               rtol=1e-4)


def test_ssd_chunked_matches_sequential():
    """The chunked SSD algorithm == the O(S) recurrence (math check)."""
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    b, s, h, p, g, n = 2, 96, 2, 32, 1, 16
    x = jax.random.normal(k1, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k2, (b, s, h))) * 0.1
    A = -jnp.exp(jax.random.normal(k3, (h,)) * 0.5)
    B = jax.random.normal(k4, (b, s, g, n)) * 0.3
    C = jax.random.normal(k1, (b, s, g, n)) * 0.3
    y_c, st_c = ref.ssd_ref(x, dt, A, B, C, 32)
    y_s, st_s = ref.ssd_sequential_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_c), np.asarray(st_s), atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(n_clients=st.integers(2, 8), t=st.sampled_from([64, 128]),
       d=st.sampled_from([64, 256]), seed=st.integers(0, 99))
def test_grad_agg_property(n_clients, t, d, seed):
    k = jax.random.key(seed)
    g = jax.random.normal(k, (n_clients, t, d), jnp.float32)
    rho = jax.nn.softmax(jax.random.normal(jax.random.key(seed + 1),
                                           (n_clients,)))
    out = ops.grad_agg(g, rho)
    exp = ref.grad_agg_ref(g, rho)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5,
                               rtol=1e-5)


def test_grad_agg_dtypes():
    for dt in (jnp.float32, jnp.bfloat16):
        g = jax.random.normal(KEY, (4, 128, 128), dt)
        rho = jnp.full((4,), 0.25, jnp.float32)
        out = ops.grad_agg(g, rho)
        exp = ref.grad_agg_ref(g, rho)
        tol = 1e-2 if dt == jnp.bfloat16 else 1e-6
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32), atol=tol)


def test_kernel_import_initialises_no_backend():
    """Importing the kernels picks no device: whether a kernel compiles
    or is interpreted is decided when it is traced, so a process may
    import them before it has chosen its backend."""
    import os
    import subprocess
    import sys

    script = (
        "from jax._src import xla_bridge as xb\n"
        "import repro.kernels.ops, repro.kernels.quantize, "
        "repro.kernels.grad_agg\n"
        "assert not xb._backends, sorted(xb._backends)\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]

