"""The chunked SSD keeps B and C in group form.

``ssd_chunked`` forms ``C B^T`` once a group and chunk instead of repeating
B and C over the heads of a group. Held here against a copy of the
per-head form it replaced: the same values, final state and gradients at
any number of groups, and far fewer FLOPs in XLA's count with one group.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.ssm import F32, segsum, ssd_chunked


def ssd_per_head(x, dt, A, B, C, chunk, initial_state=None):
    """The per-head chunked SSD: B and C repeated over each group's heads."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    c = s // chunk
    rep = h // g
    dtf = dt.astype(jnp.float32)
    xdt = x.astype(jnp.float32) * dtf[..., None]
    dA = dtf * A.astype(jnp.float32)
    Bf = jnp.repeat(B.astype(jnp.float32), rep, axis=2)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=2)
    xc = xdt.reshape(b, c, chunk, h, p)
    Bc = Bf.reshape(b, c, chunk, h, n)
    Cc = Cf.reshape(b, c, chunk, h, n)
    Ac = dA.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)
    A_cs = jnp.cumsum(Ac, axis=-1)
    L = jnp.exp(segsum(Ac))
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", Cc, Bc, L, xc,
                        precision=F32)
    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", Bc, decay_states, xc,
                        precision=F32)
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)
    states = jnp.concatenate([initial_state[:, None].astype(jnp.float32),
                              states], 1)
    chunk_decay = jnp.exp(segsum(jnp.pad(A_cs[..., -1],
                                         ((0, 0), (0, 0), (1, 0)))))
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states,
                            precision=F32)
    prev_states, final_state = new_states[:, :-1], new_states[:, -1]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", Cc, prev_states,
                       jnp.exp(A_cs), precision=F32)
    y = (y_diag + y_off).reshape(b, s, h, p)
    return y.astype(x.dtype), final_state


def _inputs(b, s, h, p, g, n, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h))) * 0.1
    A = -jnp.exp(jax.random.normal(k[2], (h,)) * 0.5)
    B = jax.random.normal(k[3], (b, s, g, n)) * 0.3
    C = jax.random.normal(k[4], (b, s, g, n)) * 0.3
    h0 = jax.random.normal(k[5], (b, h, p, n)) * 0.3
    return x, dt, A, B, C, h0


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("with_state", [False, True],
                         ids=["zero_state", "initial_state"])
@pytest.mark.parametrize("g", [1, 2, 4])
def test_group_form_matches_per_head(g, with_state):
    b, s, h, p, n, chunk = 2, 96, 4, 8, 16, 32
    x, dt, A, B, C, h0 = _inputs(b, s, h, p, g, n, seed=g)
    init = h0 if with_state else None
    wy = jax.random.normal(jax.random.key(11), (b, s, h, p))
    ws = jax.random.normal(jax.random.key(12), (b, h, p, n))

    def run(fn):
        def loss(x, dt, B, C):
            y, st = fn(x, dt, A, B, C, chunk, init)
            return jnp.sum(y * wy) + jnp.sum(st * ws)
        y, st = fn(x, dt, A, B, C, chunk, init)
        return (y, st) + jax.grad(loss, argnums=(0, 1, 2, 3))(x, dt, B, C)

    for got, want in zip(run(ssd_chunked), run(ssd_per_head)):
        _close(got, want)


def _fwd_bwd_flops(fn, b, s, h, p, g, n, chunk):
    sd = jax.ShapeDtypeStruct

    def loss(x, dt, A, B, C):
        y, st = fn(x, dt, A, B, C, chunk)
        return jnp.sum(y) + jnp.sum(st)

    ca = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4))).lower(
        sd((b, s, h, p), jnp.float32), sd((b, s, h), jnp.float32),
        sd((h,), jnp.float32), sd((b, s, g, n), jnp.float32),
        sd((b, s, g, n), jnp.float32)).compile().cost_analysis()
    return (ca[0] if isinstance(ca, list) else ca)["flops"]


def test_one_group_computes_cb_once():
    """With one group, forward and backward cost at least 35 % fewer FLOPs
    than the per-head form: C B^T and its two gradients are formed once,
    not once a head."""
    shape = dict(b=1, s=256, h=8, p=16, g=1, n=32, chunk=128)
    group = _fwd_bwd_flops(ssd_chunked, **shape)
    per_head = _fwd_bwd_flops(ssd_per_head, **shape)
    assert group <= 0.65 * per_head, (group, per_head)
