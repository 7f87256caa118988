"""Decoder stack: layer plans, scan-over-layers, train/prefill/decode.

Layers are grouped into maximal runs with identical structure ("specs");
each group is executed with ``lax.scan`` over stacked parameters so HLO size
is O(groups), not O(layers) — essential for compiling 61-layer trillion-
parameter configs 80 times in the dry-run matrix.

A "spec" is (mixer, ffn) with mixer in {attn, ssm} and ffn in
{dense, moe, none}. Hybrids (jamba) produce a periodic spec pattern that
becomes one scan group with a multi-sublayer body.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, PeftSpec
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.blocks import (init_lora, init_mlp, init_rmsnorm,
                                 merge_lora, mlp, rmsnorm)

Spec = Tuple[str, str]  # (mixer, ffn)


def layer_specs(cfg: ModelConfig) -> List[Spec]:
    out = []
    for i in range(cfg.num_layers):
        mixer = "attn" if cfg.is_attn_layer(i) else "ssm"
        if cfg.arch_type == "ssm":
            ffn = "none"
        else:
            ffn = "moe" if cfg.is_moe_layer(i) else "dense"
        out.append((mixer, ffn))
    return out


@dataclass(frozen=True)
class LayerGroup:
    repeat: int  # scan length
    period: Tuple[Spec, ...]  # sublayer specs within one scan step


def group_specs(specs: Sequence[Spec], max_period: int = 8) -> List[LayerGroup]:
    """Greedy: peel non-periodic prefix layers, then one periodic scan group."""
    n = len(specs)
    for prefix in range(0, min(3, n)):
        rest = specs[prefix:]
        for p in range(1, max_period + 1):
            if len(rest) % p:
                continue
            if all(rest[i] == rest[i % p] for i in range(len(rest))):
                groups = [LayerGroup(1, (s,)) for s in specs[:prefix]]
                groups.append(LayerGroup(len(rest) // p, tuple(rest[:p])))
                return groups
    return [LayerGroup(1, (s,)) for s in specs]  # fallback: no scan


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _init_sublayer(key, cfg: ModelConfig, spec: Spec, dtype):
    mixer, ffn = spec
    p = {"norm1": init_rmsnorm(cfg.d_model, dtype)}
    k1, k2 = jax.random.split(key)
    if mixer == "attn":
        p["attn"] = attn_mod.init_attention(k1, cfg, dtype)
    else:
        p["ssm"] = ssm_mod.init_mamba2(k1, cfg, dtype)
    if ffn != "none" and not (cfg.parallel_block and mixer == "attn"):
        p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
    if ffn == "dense":
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_act, cfg.mlp_bias, dtype)
    elif ffn == "moe":
        p["moe"] = moe_mod.init_moe(k2, cfg, dtype)
    return p


def init_groups(key, cfg: ModelConfig, groups: Sequence[LayerGroup], dtype):
    """Returns a list of stacked param trees, one per group."""
    out = []
    for g in groups:
        key, sub = jax.random.split(key)

        def one_layer(k):
            ks = jax.random.split(k, len(g.period))
            return tuple(_init_sublayer(ks[i], cfg, s, dtype)
                         for i, s in enumerate(g.period))

        keys = jax.random.split(sub, g.repeat)
        out.append(jax.vmap(one_layer)(keys))
    return out


# ---------------------------------------------------------------------------
# LoRA adapters (DESIGN.md §17)
#
# Adapter trees MIRROR the group param trees: a list of stacked trees, one
# per group, tuple-per-period, nested dicts — but each targeted linear is
# replaced by its ``{"a","b","s"}`` factor dict and everything untargeted is
# simply absent. Because the shapes stack/scan exactly like base params, the
# whole bank / resplit / aggregation machinery applies to adapters unchanged.
# ---------------------------------------------------------------------------

def lora_target_dims(cfg: ModelConfig, spec: Spec,
                     peft: PeftSpec) -> dict:
    """(d_in, d_out) per targeted projection of one sublayer, keyed like the
    param tree (``{"attn": {"wq": ...}}``). Single source of truth for both
    adapter init and the analytic traffic/param counts."""
    mixer, ffn = spec
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    out: dict = {}
    if mixer == "attn" and "attn" in peft.targets:
        out["attn"] = {
            "wq": (d, cfg.num_heads * hd),
            "wk": (d, cfg.num_kv_heads * hd),
            "wv": (d, cfg.num_kv_heads * hd),
            "wo": (cfg.num_heads * hd, d),
        }
    if mixer == "ssm" and "ssm" in peft.targets:
        s = cfg.ssm
        d_inner = s.expand * d
        heads = d_inner // s.head_dim
        d_in_proj = 2 * d_inner + 2 * s.n_groups * s.state_dim + heads
        out["ssm"] = {"in_proj": (d, d_in_proj), "out_proj": (d_inner, d)}
    if ffn == "dense" and "mlp" in peft.targets:
        mats = {"up": (d, cfg.d_ff), "down": (cfg.d_ff, d)}
        if cfg.mlp_act == "swiglu":
            mats["gate"] = (d, cfg.d_ff)
        out["mlp"] = mats
    if ffn == "moe" and "router" in peft.targets:
        out["moe"] = {"router": (d, cfg.moe.num_experts)}
    return out


def lora_numel(cfg: ModelConfig, spec: Spec, peft: PeftSpec) -> int:
    """Exact trainable-leaf count of one sublayer's adapters, including the
    scalar scale leaf — must match ``init_sublayer_lora`` element for
    element so modeled wire/migration bits reconcile with the measured
    ledger."""
    n = 0
    for mats in lora_target_dims(cfg, spec, peft).values():
        for d_in, d_out in mats.values():
            n += peft.rank * (d_in + d_out) + 1  # A + B + s
    return n


def init_sublayer_lora(key, cfg: ModelConfig, spec: Spec, peft: PeftSpec,
                       dtype):
    p: dict = {}
    for name, mats in sorted(lora_target_dims(cfg, spec, peft).items()):
        key, sub = jax.random.split(key)
        ks = jax.random.split(sub, len(mats))
        p[name] = {m: init_lora(ks[i], dims[0], dims[1], peft.rank,
                                peft.alpha, dtype)
                   for i, (m, dims) in enumerate(sorted(mats.items()))}
    return p


def init_group_loras(key, cfg: ModelConfig, groups: Sequence[LayerGroup],
                     peft: PeftSpec, dtype):
    """Stacked adapter trees, one per group — same key-split/vmap pattern as
    :func:`init_groups` so layouts line up leaf for leaf."""
    out = []
    for g in groups:
        key, sub = jax.random.split(key)

        def one_layer(k):
            ks = jax.random.split(k, len(g.period))
            return tuple(init_sublayer_lora(ks[i], cfg, s, peft, dtype)
                         for i, s in enumerate(g.period))

        keys = jax.random.split(sub, g.repeat)
        out.append(jax.vmap(one_layer)(keys))
    return out


def _is_adapter(node) -> bool:
    return isinstance(node, dict) and set(node) == {"a", "b", "s"}


def _walk_attach(base, ad):
    if _is_adapter(ad):
        return dict(base, lora=ad)
    if isinstance(ad, dict):
        return {k: _walk_attach(base[k], ad[k]) if k in ad else base[k]
                for k in base}
    if isinstance(ad, (tuple, list)):
        return type(ad)(_walk_attach(b, a) for b, a in zip(base, ad))
    return base


def attach_group_loras(params_list, lora_list):
    """Structurally merge adapters into base group params: every targeted
    linear dict gains a ``"lora"`` entry that :func:`repro.models.blocks.
    linear` applies on the factored path. Trace-time dict surgery — no
    copies, no extra ops on untargeted leaves."""
    return [_walk_attach(gp, la) for gp, la in zip(params_list, lora_list)]


def _walk_merge(base, ad):
    if _is_adapter(ad):
        return merge_lora(base, ad)
    if isinstance(ad, dict):
        return {k: _walk_merge(base[k], ad[k]) if k in ad else base[k]
                for k in base}
    if isinstance(ad, (tuple, list)):
        return type(ad)(_walk_merge(b, a) for b, a in zip(base, ad))
    return base


def merge_group_loras(params_list, lora_list):
    """Fold adapters into the base weights (w' = w + s·AB), returning
    base-shaped group params — for serving/eval or parity against the
    full-parameter path."""
    return [_walk_merge(gp, la) for gp, la in zip(params_list, lora_list)]


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _res(cfg, y):
    """A sublayer's output as it joins the residual stream."""
    if cfg.residual_multiplier == 1.0:
        return y
    return y * cfg.residual_multiplier


def _sublayer_train(cfg, spec, p, x, positions, impl):
    mixer, ffn = spec
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if cfg.parallel_block and mixer == "attn" and ffn == "dense":
        a = attn_mod.attend_train(p["attn"], cfg, h, positions, impl)
        m = mlp(p["mlp"], h, cfg.mlp_act)
        return x + _res(cfg, a) + _res(cfg, m), aux
    if mixer == "attn":
        x = x + _res(cfg, attn_mod.attend_train(p["attn"], cfg, h, positions,
                                                impl))
    else:
        x = x + _res(cfg, ssm_mod.mamba2_train(p["ssm"], cfg, h,
                                               use_kernel=(impl == "flash")))
    if ffn == "dense":
        x = x + _res(cfg, mlp(p["mlp"], rmsnorm(p["norm2"], x, cfg.norm_eps),
                              cfg.mlp_act))
    elif ffn == "moe":
        y, aux = moe_mod.moe_apply(p["moe"], cfg, rmsnorm(p["norm2"], x, cfg.norm_eps))
        x = x + _res(cfg, y)
    return x, aux


def apply_groups_train(params_list, cfg: ModelConfig, groups, x, positions,
                       impl: str = "jnp", remat: bool = True):
    """Full-sequence forward through all groups. Returns (x, aux_sum)."""
    aux_total = jnp.zeros((), jnp.float32)
    for g, gp in zip(groups, params_list):

        def body(carry, layer_p):
            xc, aux = carry
            for i, s in enumerate(g.period):
                xc, a = _sublayer_train(cfg, s, layer_p[i], xc, positions, impl)
                aux = aux + a
            return (xc, aux), None

        if remat:
            body = jax.checkpoint(body)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), gp)
    return x, aux_total


def _sublayer_prefill(cfg, spec, p, x, positions, max_len, impl):
    mixer, ffn = spec
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if cfg.parallel_block and mixer == "attn" and ffn == "dense":
        a, cache = attn_mod.attend_prefill(p["attn"], cfg, h, positions, max_len, impl)
        m = mlp(p["mlp"], h, cfg.mlp_act)
        return x + _res(cfg, a) + _res(cfg, m), cache
    if mixer == "attn":
        out, cache = attn_mod.attend_prefill(p["attn"], cfg, h, positions, max_len, impl)
        x = x + _res(cfg, out)
    else:
        out, cache = ssm_mod.mamba2_prefill(p["ssm"], cfg, h, use_kernel=(impl == "flash"))
        x = x + _res(cfg, out)
    if ffn == "dense":
        x = x + _res(cfg, mlp(p["mlp"], rmsnorm(p["norm2"], x, cfg.norm_eps),
                              cfg.mlp_act))
    elif ffn == "moe":
        y, _ = moe_mod.moe_apply(p["moe"], cfg, rmsnorm(p["norm2"], x, cfg.norm_eps))
        x = x + _res(cfg, y)
    return x, cache


def apply_groups_prefill(params_list, cfg, groups, x, positions, max_len,
                         impl: str = "jnp"):
    """Returns (x, caches) — caches: list (per group) of stacked per-layer trees."""
    caches = []
    for g, gp in zip(groups, params_list):

        def body(xc, layer_p):
            layer_caches = []
            for i, s in enumerate(g.period):
                xc, c = _sublayer_prefill(cfg, s, layer_p[i], xc, positions,
                                          max_len, impl)
                layer_caches.append(c)
            return xc, tuple(layer_caches)

        x, gc = jax.lax.scan(body, x, gp)
        caches.append(gc)
    return x, caches


def _attend_decode_any(p, cfg, h, cache, impl):
    """Dispatch on cache type: a PagedKVCache (continuous-batching server,
    per-slot positions) vs the dense ring-buffer KVCache (lock-step batch,
    one shared position)."""
    from repro.models import paging as paging_mod

    if isinstance(cache, paging_mod.PagedKVCache):
        return paging_mod.attend_decode_paged(p, cfg, h, cache, impl)
    return attn_mod.attend_decode(p, cfg, h, cache, impl)


def _sublayer_decode(cfg, spec, p, x, cache, impl):
    mixer, ffn = spec
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if cfg.parallel_block and mixer == "attn" and ffn == "dense":
        a, cache = _attend_decode_any(p["attn"], cfg, h, cache, impl)
        m = mlp(p["mlp"], h, cfg.mlp_act)
        return x + _res(cfg, a) + _res(cfg, m), cache
    if mixer == "attn":
        out, cache = _attend_decode_any(p["attn"], cfg, h, cache, impl)
        x = x + _res(cfg, out)
    else:
        out, cache = ssm_mod.mamba2_decode(p["ssm"], cfg, h, cache)
        x = x + _res(cfg, out)
    if ffn == "dense":
        x = x + _res(cfg, mlp(p["mlp"], rmsnorm(p["norm2"], x, cfg.norm_eps),
                              cfg.mlp_act))
    elif ffn == "moe":
        y, _ = moe_mod.moe_apply(p["moe"], cfg, rmsnorm(p["norm2"], x, cfg.norm_eps))
        x = x + _res(cfg, y)
    return x, cache


def apply_groups_decode(params_list, cfg, groups, x, caches, impl: str = "jnp"):
    """One-token decode. Returns (x, new_caches)."""
    new_caches = []
    for g, gp, gc in zip(groups, params_list, caches):

        def body(xc, scanned):
            layer_p, layer_c = scanned
            outs = []
            for i, s in enumerate(g.period):
                xc, c = _sublayer_decode(cfg, s, layer_p[i], xc, layer_c[i], impl)
                outs.append(c)
            return xc, tuple(outs)

        x, nc = jax.lax.scan(body, x, (gp, gc))
        new_caches.append(nc)
    return x, new_caches


def init_group_caches(cfg: ModelConfig, groups, batch: int, max_len: int, dtype):
    """Cache skeleton matching apply_groups_decode's expectations."""
    caches = []
    for g in groups:
        per_layer = []
        for s in g.period:
            if s[0] == "attn":
                per_layer.append(attn_mod.init_cache(cfg, batch, max_len, dtype))
            else:
                per_layer.append(ssm_mod.init_ssm_cache(cfg, batch, dtype))
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (g.repeat,) + a.shape), tuple(per_layer))
        caches.append(stacked)
    return caches
