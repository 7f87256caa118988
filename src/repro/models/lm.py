"""Top-level language model: embeddings -> layer groups -> norm -> logits.

``ModelPlan`` freezes everything static (config, cut point, layer grouping)
so the same plan object drives init, train, prefill and decode — and so the
SFL split (client side = embed + layers[:cut], server side = rest + head)
is a first-class structural property, not an afterthought.

Inputs may be token ids or precomputed embeddings (VLM patch embeddings /
whisper frame embeddings — the stubbed modality frontends).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, PeftSpec
from repro.models import transformer as tf
from repro.models.blocks import cast_tree, embed, init_embedding, init_linear, init_rmsnorm, linear, rmsnorm, unembed


@dataclass(frozen=True)
class ModelPlan:
    cfg: ModelConfig
    cut: int  # 0 = no split (everything server-side); v in [1, L-1] for SFL
    client_groups: Tuple[tf.LayerGroup, ...]
    server_groups: Tuple[tf.LayerGroup, ...]
    # PEFT: when set, the federated/trainable unit is the adapter tree and
    # the init_lm tree above is a frozen base (DESIGN.md §17). None keeps
    # every full-parameter code path byte-identical to the pre-PEFT repo.
    peft: Optional[PeftSpec] = None

    @property
    def num_layers(self) -> int:
        return self.cfg.num_layers


def build_plan(cfg: ModelConfig, cut: int = 0,
               peft: Optional[PeftSpec] = None) -> ModelPlan:
    specs = tf.layer_specs(cfg)
    assert 0 <= cut < cfg.num_layers, (cut, cfg.num_layers)
    cg = tuple(tf.group_specs(specs[:cut])) if cut else ()
    sg = tuple(tf.group_specs(specs[cut:]))
    return ModelPlan(cfg=cfg, cut=cut, client_groups=cg, server_groups=sg,
                     peft=peft)


def init_lm(key, plan: ModelPlan, dtype=jnp.float32):
    cfg = plan.cfg
    ke, kc, ks, kn, kh = jax.random.split(key, 5)
    params = {
        "embed": init_embedding(ke, cfg.vocab_size, cfg.d_model, dtype),
        "client": tf.init_groups(kc, cfg, plan.client_groups, dtype),
        "server": tf.init_groups(ks, cfg, plan.server_groups, dtype),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
    }
    # Tied embeddings are untied when the model is split: the embedding
    # lives client-side, the head server-side (they can no longer share).
    if not cfg.tie_embeddings or plan.cut >= 1:
        params["head"] = init_linear(kh, cfg.d_model, cfg.vocab_size, False, dtype)
    return params


def init_lm_loras(key, plan: ModelPlan, dtype=jnp.float32):
    """Adapter trees for a PEFT plan: ``{"client": [...], "server": [...]}``
    group lists mirroring :func:`init_lm`'s stacking. Embedding, norms and
    head carry no adapters — they stay frozen with the base."""
    assert plan.peft is not None, "init_lm_loras needs a plan with peft set"
    kc, ks = jax.random.split(key)
    return {
        "client": tf.init_group_loras(kc, plan.cfg, plan.client_groups,
                                      plan.peft, dtype),
        "server": tf.init_group_loras(ks, plan.cfg, plan.server_groups,
                                      plan.peft, dtype),
    }


def attach_lm_loras(base, loras):
    """init_lm-shaped tree with adapters attached on both halves — the
    forward-ready view of (frozen base, trainable adapters)."""
    return dict(
        base,
        client=tf.attach_group_loras(base["client"], loras["client"]),
        server=tf.attach_group_loras(base["server"], loras["server"]),
    )


def merge_lm_loras(base, loras):
    """Fold adapters into the frozen base: a plain full-parameter tree
    (w' = w + s·AB) usable by every non-PEFT code path."""
    return dict(
        base,
        client=tf.merge_group_loras(base["client"], loras["client"]),
        server=tf.merge_group_loras(base["server"], loras["server"]),
    )


def _positions(cfg: ModelConfig, B: int, S: int, offset: int = 0):
    pos = jnp.arange(S, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.mrope_sections:
        # text-only default: all three planes share the linear position.
        pos = jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def embed_tokens(params, cfg: ModelConfig, tokens, dtype=jnp.bfloat16):
    """The token lookup, times the configuration's embedding multiplier."""
    x = embed(params["embed"], tokens, dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _inputs(params, cfg, tokens=None, inputs_embeds=None, dtype=jnp.bfloat16):
    if inputs_embeds is not None:
        return inputs_embeds
    return embed_tokens(params, cfg, tokens, dtype)


def logits_from_hidden(params, cfg: ModelConfig, x):
    """Final norm and head (or the tied table), divided by the
    configuration's logits scaling."""
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if "head" in params:
        logits = linear(params["head"], x)
    else:
        logits = unembed(params["embed"], x)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


# ---------------------------------------------------------------------------
# Training-mode forward, split into client/server halves (the SFL boundary)
# ---------------------------------------------------------------------------

def client_forward(params, plan: ModelPlan, tokens=None, inputs_embeds=None,
                   positions=None, impl="jnp", remat=True, dtype=jnp.bfloat16):
    """Client-side model: embed + layers[:cut]. Output = smashed data (eq. 1)."""
    cfg = plan.cfg
    x = _inputs(params, cfg, tokens, inputs_embeds, dtype)
    B, S = x.shape[:2]
    if positions is None:
        positions = _positions(cfg, B, S)
    x, aux = tf.apply_groups_train(params["client"], cfg, plan.client_groups,
                                   x, positions, impl, remat)
    return x, aux


def server_forward(params, plan: ModelPlan, smashed, positions=None,
                   impl="jnp", remat=True):
    """Server-side model: layers[cut:] + norm + head. Returns logits."""
    cfg = plan.cfg
    B, S = smashed.shape[:2]
    if positions is None:
        positions = _positions(cfg, B, S)
    x, aux = tf.apply_groups_train(params["server"], cfg, plan.server_groups,
                                   smashed, positions, impl, remat)
    return logits_from_hidden(params, cfg, x), aux


def lm_loss(params, plan: ModelPlan, tokens=None, labels=None, inputs_embeds=None,
            impl="jnp", remat=True, boundary_fn=None, dtype=jnp.bfloat16,
            aux_weight: float = 0.01):
    """Full train loss. ``boundary_fn`` is applied to the smashed data —
    this is where the SFL-GA gradient-aggregation op plugs in."""
    smashed, aux_c = client_forward(params, plan, tokens, inputs_embeds,
                                    impl=impl, remat=remat, dtype=dtype)
    if boundary_fn is not None:
        smashed = boundary_fn(smashed)
    logits, aux_s = server_forward(params, plan, smashed, impl=impl, remat=remat)
    loss = cross_entropy(logits, labels)
    return loss + aux_weight * (aux_c + aux_s), (loss, aux_c + aux_s)


def cross_entropy(logits, labels, ignore_id: int = -1):
    """Mean token CE in fp32. labels: (B, S) int32, ignore_id masked out."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum((lse - ll) * mask) / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Serving-mode: prefill + decode (split is a training concept; serving uses
# the composed model)
# ---------------------------------------------------------------------------

def all_groups(plan: ModelPlan):
    return tuple(plan.client_groups) + tuple(plan.server_groups)


def all_group_params(params):
    return list(params["client"]) + list(params["server"])


def prefill(params, plan: ModelPlan, tokens=None, inputs_embeds=None,
            max_len: Optional[int] = None, impl="jnp", dtype=jnp.bfloat16):
    cfg = plan.cfg
    x = _inputs(params, cfg, tokens, inputs_embeds, dtype)
    B, S = x.shape[:2]
    max_len = max_len or S
    positions = _positions(cfg, B, S)
    ng = len(plan.client_groups)
    x, caches = tf.apply_groups_prefill(all_group_params(params), cfg,
                                        all_groups(plan), x, positions,
                                        max_len, impl)
    logits = logits_from_hidden(params, cfg, x[:, -1:, :])
    return logits, caches


def decode_step(params, plan: ModelPlan, token, caches, impl="jnp",
                dtype=jnp.bfloat16):
    """token: (B, 1) int32 (or (B,1,d) embeds). One step; returns (logits, caches)."""
    cfg = plan.cfg
    if token.ndim == 2:
        x = embed_tokens(params, cfg, token, dtype)
    else:
        x = token
    x, caches = tf.apply_groups_decode(all_group_params(params), cfg,
                                       all_groups(plan), x, caches, impl)
    return logits_from_hidden(params, cfg, x), caches


def init_caches(plan: ModelPlan, batch: int, max_len: int, dtype=jnp.bfloat16):
    return tf.init_group_caches(plan.cfg, all_groups(plan), batch, max_len, dtype)
