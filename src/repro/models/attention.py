"""GQA attention with RoPE/M-RoPE (or none: NoPE), sliding windows, and
KV caches.

Three entry points share one parameter tree:

* ``attend_train``   — full-sequence causal (or windowed) attention.
* ``attend_prefill`` — same math, but also returns a ``KVCache``.
* ``attend_decode``  — one query token against the cache (ring-buffered for
  sliding-window models so a 524k-token stream needs only O(window) memory).

The inner product/softmax can be swapped for the Pallas flash kernel via
``impl="flash"`` (TPU target; validated in interpret mode in tests).
"""
from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks
from repro.models.blocks import apply_rope, init_linear, init_rmsnorm, linear, rmsnorm, rope_sin_cos


class KVCache(NamedTuple):
    k: jnp.ndarray  # (B, C, Hkv, D) — C = cache capacity (seq_len or window)
    v: jnp.ndarray  # (B, C, Hkv, D)
    # number of tokens ever written; ring index = length % capacity when windowed
    length: jnp.ndarray  # () int32


def init_attention(key, cfg: ModelConfig, dtype=jnp.float32):
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": init_linear(kq, cfg.d_model, cfg.num_heads * hd, cfg.attn_bias, dtype),
        "wk": init_linear(kk, cfg.d_model, cfg.num_kv_heads * hd, cfg.attn_bias, dtype),
        "wv": init_linear(kv, cfg.d_model, cfg.num_kv_heads * hd, cfg.attn_bias, dtype),
        "wo": init_linear(ko, cfg.num_heads * hd, cfg.d_model, False, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def _project_qkv(params, cfg: ModelConfig, x, positions, cross_kv_x=None):
    """q, k, v of every attention path (train, prefill, decode, paged).

    Every path divides the scores by sqrt(head_dim) (``_sdpa`` and the
    flash and paged kernels alike), so a configured ``attention_multiplier``
    is folded into q here: q * (multiplier * sqrt(hd)) gives scores
    q.k * multiplier on each of them. A NoPE layer's projections run
    under the scope ``attn/nope``, with no rotary embedding."""
    nope = cfg.position_embedding_type == "nope"
    with jax.named_scope("attn/nope") if nope else contextlib.nullcontext():
        B = x.shape[0]
        hd = cfg.resolved_head_dim
        q = linear(params["wq"], x).reshape(B, x.shape[1], cfg.num_heads, hd)
        src = cross_kv_x if cross_kv_x is not None else x
        k = linear(params["wk"], src).reshape(B, src.shape[1], cfg.num_kv_heads, hd)
        v = linear(params["wv"], src).reshape(B, src.shape[1], cfg.num_kv_heads, hd)
        if cfg.qk_norm:
            q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
            k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
        if positions is not None and not nope:  # rope (not for whisper/cross attention)
            sin, cos = rope_sin_cos(positions, hd, cfg.rope_theta, cfg.mrope_sections)
            q = apply_rope(q, sin, cos)
            if cross_kv_x is None:
                k = apply_rope(k, sin, cos)
        if cfg.attention_multiplier is not None:
            q = q * (cfg.attention_multiplier * math.sqrt(hd))
        return q, k, v


def _sdpa(q, k, v, mask, impl: str = "jnp", logit_softcap: float = 0.0):
    """q: (B,S,Hq,D), k/v: (B,T,Hkv,D); mask: (B,S,T) or (S,T) bool or None."""
    if impl == "flash" and mask is None:
        raise ValueError("flash path is selected at a higher level with static masks")
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qf = q.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bshgd,bthd->bhgst", qf, kf) / jnp.sqrt(D).astype(jnp.float32)
    if logit_softcap:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None]
        scores = jnp.where(mask[:, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", w.astype(v.dtype), v)
    return out.reshape(B, S, Hq, D)


# Above this query length the jnp paths process queries in blocks (exact
# math, O(block x T) live scores instead of O(S x T)) — the XLA-level
# analogue of the Pallas flash kernel's VMEM tiling, and what keeps the
# 32k-prefill dry-run memory term honest (EXPERIMENTS.md §Perf pair D).
Q_CHUNK_THRESHOLD = 8192
Q_CHUNK_BLOCK = 2048


def _sdpa_q_chunked(q, k, v, *, window: Optional[int], logit_softcap: float,
                    block: int = Q_CHUNK_BLOCK):
    """Causal attention with the query axis processed in blocks via lax.map."""
    B, S, Hq, D = q.shape
    T = k.shape[1]
    nb = S // block

    def one(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        qi = i * block + jnp.arange(block)[:, None]
        kj = jnp.arange(T)[None, :]
        m = kj <= qi
        if window is not None:
            m &= kj > qi - window
        return _sdpa(qs, k, v, m, logit_softcap=logit_softcap)

    out = jax.lax.map(one, jnp.arange(nb))  # (nb, B, block, Hq, D)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, Hq, D)


def _maybe_chunked_causal(q, k, v, window, logit_softcap):
    S, T = q.shape[1], k.shape[1]
    if S == T and S >= Q_CHUNK_THRESHOLD and S % Q_CHUNK_BLOCK == 0:
        return _sdpa_q_chunked(q, k, v, window=window,
                               logit_softcap=logit_softcap)
    return _sdpa(q, k, v, causal_mask(S, T, window=window),
                 logit_softcap=logit_softcap)


def causal_mask(S: int, T: int, offset: int = 0, window: Optional[int] = None):
    """(S, T) bool; query i attends key j iff j <= i+offset and within window."""
    qi = jnp.arange(S)[:, None] + offset
    kj = jnp.arange(T)[None, :]
    m = kj <= qi
    if window is not None:
        m &= kj > qi - window
    return m


def attend_train(params, cfg: ModelConfig, x, positions, impl: str = "jnp",
                 causal: bool = True, cross_kv_x=None):
    q, k, v = _project_qkv(params, cfg, x, positions, cross_kv_x)
    if impl == "flash" and causal and cross_kv_x is None:
        from repro.kernels import ops as kops

        out = kops.flash_attention(q, k, v, causal=True, window=cfg.sliding_window)
    elif causal and cross_kv_x is None:
        out = _maybe_chunked_causal(q, k, v, cfg.sliding_window,
                                    cfg.logit_softcap)
    else:
        out = _sdpa(q, k, v, None, logit_softcap=cfg.logit_softcap)
    B, S = x.shape[:2]
    return linear(params["wo"], out.reshape(B, S, -1))


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> KVCache:
    cap = max_len if cfg.sliding_window is None else min(max_len, cfg.sliding_window)
    hd = cfg.resolved_head_dim
    z = jnp.zeros((batch, cap, cfg.num_kv_heads, hd), dtype)
    return KVCache(z, z, jnp.zeros((), jnp.int32))


def attend_prefill(params, cfg: ModelConfig, x, positions, max_len: int,
                   impl: str = "jnp"):
    """Run full-sequence attention and build the cache for later decode."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = _maybe_chunked_causal(q, k, v, cfg.sliding_window, cfg.logit_softcap)
    B, S = x.shape[:2]
    cache = init_cache(cfg, B, max_len, k.dtype)
    cap = cache.k.shape[1]
    if S >= cap:  # keep the last `cap` keys (ring buffer laid out by position % cap)
        idx = (jnp.arange(S - cap, S)) % cap
        cache = KVCache(
            cache.k.at[:, idx].set(k[:, S - cap:]),
            cache.v.at[:, idx].set(v[:, S - cap:]),
            jnp.asarray(S, jnp.int32),
        )
    else:
        cache = KVCache(
            jax.lax.dynamic_update_slice(cache.k, k, (0, 0, 0, 0)),
            jax.lax.dynamic_update_slice(cache.v, v, (0, 0, 0, 0)),
            jnp.asarray(S, jnp.int32),
        )
    return linear(params["wo"], out.reshape(B, S, -1)), cache


def attend_decode(params, cfg: ModelConfig, x, cache: KVCache, impl: str = "jnp",
                  cross: bool = False):
    """One-token decode. x: (B, 1, d). Returns (y, new_cache).

    For cross-attention (whisper decoder) the cache is the projected encoder
    KV and is not updated.
    """
    B = x.shape[0]
    pos = cache.length  # scalar position of the new token
    positions = jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(pos, (3, B, 1)).astype(jnp.int32)
    q, k, v = _project_qkv(params, cfg, x, None if cross else positions,
                           cross_kv_x=None)
    if cross:
        out = _sdpa(q, cache.k, cache.v, None, logit_softcap=cfg.logit_softcap)
        return linear(params["wo"], out.reshape(B, 1, -1)), cache
    cap = cache.k.shape[1]
    slot = (pos % cap).astype(jnp.int32)
    new_k = jax.lax.dynamic_update_slice(cache.k, k, (0, slot, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache.v, v, (0, slot, 0, 0))
    # validity: entry j holds absolute position; with ring layout, entry j is
    # valid iff it was written, i.e. j < length+1 (unwindowed) or always once full.
    written = jnp.arange(cap) <= jnp.minimum(pos, cap - 1)
    if cfg.sliding_window is not None:
        valid = written  # ring keeps exactly the last `cap` positions
    else:
        valid = written
    mask = jnp.broadcast_to(valid[None, None, :], (B, 1, cap))
    out = _sdpa(q, new_k, new_v, mask, logit_softcap=cfg.logit_softcap)
    return (linear(params["wo"], out.reshape(B, 1, -1)),
            KVCache(new_k, new_v, pos + 1))


def attn_flops_per_token(cfg: ModelConfig, context: int) -> int:
    """Projections + score/value matmuls at a given context length."""
    hd = cfg.resolved_head_dim
    proj = 2 * cfg.d_model * hd * (2 * cfg.num_heads + 2 * cfg.num_kv_heads)
    ctx = min(context, cfg.sliding_window) if cfg.sliding_window else context
    sdp = 2 * 2 * cfg.num_heads * hd * ctx
    return proj + sdp
