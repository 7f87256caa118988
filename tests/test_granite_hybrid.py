"""Granite 4.0-H knobs and the granite-4.0-h-micro preset.

NoPE and the attention scale hold on the training paths (jnp and
flash; decode and paged in ``test_paged_attention.py``); the embedding,
residual and logits multipliers do what their names say; the preset has
the published layer pattern and sizes."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import attention as attn_mod
from repro.models import lm
from repro.models import transformer as tf
from repro.models.blocks import linear

PRESET = get_config("granite-4.0-h-micro")
SMALL = reduced_config(PRESET)
PLAIN = dict(position_embedding_type="rope", embedding_multiplier=1.0,
             residual_multiplier=1.0, attention_multiplier=None,
             logits_scaling=1.0)


def _attn(cfg, S=24, B=2, seed=0):
    ka, kx = jax.random.split(jax.random.key(seed))
    return (attn_mod.init_attention(ka, cfg),
            jax.random.normal(kx, (B, S, cfg.d_model)))


# ------------------------------------------------------------- attention
def test_nope_attention_ignores_positions():
    params, x = _attn(SMALL)
    pos = jnp.arange(x.shape[1])[None, :]
    # RoPE sees relative positions: stretch them, not shift them
    a = attn_mod.attend_train(params, SMALL, x, pos)
    b = attn_mod.attend_train(params, SMALL, x, 3 * pos)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rope = dataclasses.replace(SMALL, position_embedding_type="rope")
    a = attn_mod.attend_train(params, rope, x, pos)
    b = attn_mod.attend_train(params, rope, x, 3 * pos)
    assert not np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("mult", [None, 0.015625, 0.3])
def test_attention_multiplier_scales_the_scores(mult):
    cfg = dataclasses.replace(SMALL, attention_multiplier=mult)
    params, x = _attn(cfg)
    B, S, _ = x.shape
    hd, G = cfg.resolved_head_dim, cfg.num_heads // cfg.num_kv_heads
    q = linear(params["wq"], x).reshape(B, S, cfg.num_heads, hd)
    k = jnp.repeat(linear(params["wk"], x).reshape(B, S, -1, hd), G, 2)
    v = jnp.repeat(linear(params["wv"], x).reshape(B, S, -1, hd), G, 2)
    scale = 1 / math.sqrt(hd) if mult is None else mult
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    want = linear(params["wo"], o.reshape(B, S, -1))
    got = attn_mod.attend_train(params, cfg, x, jnp.arange(S)[None, :])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_under_granite_knobs():
    """The flash kernel against full-sequence jnp attention, with NoPE and
    the 1/head_dim scale (the preset's); the decode and paged paths are
    checked in ``test_paged_attention.py``."""
    S = 128  # the kernel's blocks
    params, x = _attn(SMALL, S=S, B=1)
    pos = jnp.arange(S)[None, :]
    want = attn_mod.attend_train(params, SMALL, x, pos)
    got = attn_mod.attend_train(params, SMALL, x, pos, impl="flash")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-5, atol=5e-5)


# ------------------------------------------------------ LM multipliers
def _lm(cfg, cut=0):
    plan = lm.build_plan(cfg, cut)
    return plan, lm.init_lm(jax.random.key(3), plan, jnp.float32)


def test_embedding_and_logits_multipliers():
    plan, params = _lm(SMALL)
    plain = dataclasses.replace(SMALL, **PLAIN)
    toks = jnp.arange(12)[None, :]
    np.testing.assert_array_equal(
        np.asarray(lm.embed_tokens(params, SMALL, toks, jnp.float32)),
        12.0 * np.asarray(lm.embed_tokens(params, plain, toks, jnp.float32)))
    h = jax.random.normal(jax.random.key(4), (1, 5, SMALL.d_model))
    np.testing.assert_array_equal(
        np.asarray(lm.logits_from_hidden(params, SMALL, h)),
        np.asarray(lm.logits_from_hidden(params, plain, h)) / 8.0)


def test_residual_multiplier_weights_every_sublayer():
    """With a residual multiplier of 0 every layer, Mamba-2 and attention,
    passes its input through; at the preset's 0.22 none does."""
    toks = jax.random.randint(jax.random.key(5), (2, 32), 0,
                              SMALL.vocab_size)
    for r, same in ((0.0, True), (0.22, False)):
        cfg = dataclasses.replace(SMALL, residual_multiplier=r)
        plan, params = _lm(cfg, cut=1)
        x0 = lm.embed_tokens(params, cfg, toks, jnp.float32)
        x1, _ = lm.client_forward(params, plan, toks, remat=False,
                                  dtype=jnp.float32)
        x2, _ = tf.apply_groups_train(params["server"], cfg,
                                      plan.server_groups, x1, None,
                                      remat=False)
        assert np.array_equal(np.asarray(x2), np.asarray(x0)) == same, r


# ------------------------------------------------------------ the preset
def test_preset_has_the_published_pattern_and_sizes():
    specs = tf.layer_specs(PRESET)
    assert [i for i, (m, _) in enumerate(specs) if m == "attn"] == [
        5, 15, 25, 35]
    assert all(f == "dense" for _, f in specs)
    groups = tf.group_specs(specs[1:10])  # layers 1-9, attention at 5
    shapes = jax.eval_shape(
        lambda k: tf.init_groups(k, PRESET, groups, jnp.float32),
        jax.random.key(0))
    sizes = [("ssm" if "ssm" in sub else "attn",
              sum(int(np.prod(a.shape)) for a in jax.tree.leaves(sub)))
             for g in shapes for sub in g]
    mamba, attn = ("ssm", 76_182_976), ("attn", 60_821_504)
    assert sizes == [mamba] * 4 + [attn] + [mamba] * 4


def _eqns(jaxpr):
    """Every equation of a closed jaxpr, those of its sub-jaxprs too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_mamba2_ssd_and_conv_run_at_float32_precision():
    """Every contraction and the conv of the Mamba-2 mixer's SSD, in the
    forward and the backward pass, asks for HIGHEST: at the TPU's default
    a float32 matmul rounds its operands (the decays among them) to
    bfloat16. The projections keep the default."""
    from repro.models import ssm

    cfg = SMALL
    p = ssm.init_mamba2(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 2 * cfg.ssm.chunk_size,
                                              cfg.d_model))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, x: ssm.mamba2_train(p, cfg, x).sum(), argnums=(0, 1)))(
            p, x).jaxpr
    hi = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    kinds = {}
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name in ("dot_general", "conv_general_dilated"):
            prec = eqn.params["precision"]
            kinds.setdefault(eqn.primitive.name, []).append(prec == hi)
    assert kinds["conv_general_dilated"] and all(kinds["conv_general_dilated"])
    dots = kinds["dot_general"]
    # in_proj and out_proj, forward and both backward products each: 6
    assert dots.count(False) == 6 and dots.count(True) >= 8
