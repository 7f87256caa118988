"""Plain reference of a dense decoder LM split at a cut, with the int8 wire.

Written from the model's equations, in straightforward ``jax.numpy``: no
kernels, no caches, no batching tricks, and nothing imported from the
system under test. It serves two comparisons:

* ``train_step`` — sfl_ga split LoRA training (the aggregated cut-layer
  gradient broadcast, eq. 5 of arXiv:2501.01078) with SGD, step by step;
* ``serve_logits`` — the full-sequence logits of a served request, with the
  boundary activation of each position quantized exactly as the wire
  quantized it when that position was served, and each position that a
  decode step served attending as a decode step does (an online softmax
  over the cache's pages, in order).

The block: pre-RMSNorm, GQA attention with rotate-half RoPE, residual,
pre-RMSNorm, SwiGLU or tanh-GELU MLP, residual; final RMSNorm; untied head.
LoRA on every attention and MLP projection: ``y = x W + b + s (x A) B`` with
``s = alpha / rank``. The int8 wire: symmetric absmax quantization over
flat tiles of 256 elements (scale = absmax / 127), stochastic rounding by a
counter hash of the element's flat index and a 32-bit seed.

``dtype`` is the activation type. float32 is the reference: the precision
the configurations state, float32 at the chip's default matmul precision
(on a TPU one bfloat16 pass with float32 accumulation). bfloat16 is the
control, a mixed-precision step: activations and matmul operands in
bfloat16, the parameters kept in float32 and cast where they are used, so
an SGD update lands on float32 parameters. Norm statistics, attention
scores and softmax, and the cross-entropy stay in float32 in both. Attention
runs in blocks of ``Q_BLOCK`` queries, so a long sequence fits beside the
weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

TILE = 256
QMAX = 127
GOLDEN = 0x9E3779B1       # per-client seed stride of the uplink
DOWNLINK_MIX = 0x5BD1E995  # downlink seed = uplink seed ^ this
_K_D, _K_S = 0xC2B2AE3D, 0x27D4EB2F
_M1, _M2 = 0x2C1B3C6D, 0x297A2D39


# ------------------------------------------------------------------ wire
def hash_uniform(idx, seed):
    """uniform [0, 1) from a flat element index and a seed (uint32 math)."""
    u32 = jnp.uint32
    h = (idx.astype(u32) * u32(_K_D)) ^ (seed.astype(u32) * u32(_K_S))
    h = h ^ (h >> 15)
    h = h * u32(_M1)
    h = h ^ (h >> 13)
    h = h * u32(_M2)
    h = h ^ (h >> 16)
    return (h >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))


def int8_roundtrip(x, seed, offset=None):
    """Quantize and dequantize ``x`` on the int8 wire.

    ``x``: (..., n) with n a multiple of 256; the leading axes are rows of
    one payload. ``seed``: scalar, or one seed per row (shape x.shape[:-1]).
    ``offset``: the flat index of each row's first element in its payload
    (default: rows laid out back to back)."""
    shape = x.shape
    n = shape[-1]
    rows = x.reshape(-1, n).astype(jnp.float32)
    r = rows.shape[0]
    if offset is None:
        offset = jnp.arange(r, dtype=jnp.uint32) * jnp.uint32(n)
    seed = jnp.broadcast_to(jnp.asarray(seed, jnp.uint32).reshape(-1), (r,))
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.uint32).reshape(-1),
                              (r,))
    tiles = rows.reshape(r, n // TILE, TILE)
    absmax = jnp.max(jnp.abs(tiles), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0.0, absmax * (1.0 / QMAX), 1.0)
    idx = offset[:, None] + jnp.arange(n, dtype=jnp.uint32)[None, :]
    u = hash_uniform(idx, seed[:, None]).reshape(tiles.shape)
    q = jnp.clip(jnp.floor(tiles / scale + u), -QMAX, QMAX)
    return (q * scale).reshape(shape).astype(x.dtype)


# ----------------------------------------------------------------- model
Q_BLOCK = 256


def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def linear(cfg, x, p, name, lora, s):
    y = jnp.matmul(x, p[name].astype(x.dtype))
    if name + "_b" in p:
        y = y + p[name + "_b"].astype(x.dtype)
    if lora is not None and name in lora:
        y = y + jnp.matmul(jnp.matmul(x, lora[name]["a"].astype(x.dtype)),
                           lora[name]["b"].astype(x.dtype)) * \
            jnp.asarray(s, x.dtype)
    return y


def rope(x, positions, theta):
    """x: (B, T, H, D); rotate-half convention."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, T, half)
    sin, cos = jnp.sin(ang)[:, :, None], jnp.cos(ang)[:, :, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attend(q, k, v, q0):
    """Queries ``q`` (B, Tq, H, D) at positions q0.. against all keys."""
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    qi = q0 + jnp.arange(q.shape[1])[:, None]
    s = jnp.where(jnp.arange(k.shape[1])[None, :] <= qi, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _attend_decode(q, k, v, page):
    """Each query row as a decode step attends: queries scaled first, then
    an online softmax over the keys a page at a time, in order, up to and
    including its own position (pages past it are skipped)."""
    B, T, H, D = q.shape
    qf = (q.astype(jnp.float32) * (1.0 / math.sqrt(D))).astype(q.dtype)
    kp = jnp.moveaxis(k.reshape(B, T // page, page, H, D), 1, 0)
    vp = jnp.moveaxis(v.reshape(B, T // page, page, H, D), 1, 0)
    qpos = jnp.arange(T)[:, None]

    def one_page(carry, inp):
        m, l, acc = carry
        j, kb, vb = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb,
                       preferred_element_type=jnp.float32)
        pos = j * page + jnp.arange(page)[None, :]
        s = jnp.where(pos <= qpos, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        e = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(e, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhqk,bkhd->bhqd", e.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        hit = j * page <= qpos
        return (jnp.where(hit, m_new, m), jnp.where(hit, l_new, l),
                jnp.where(hit, acc_new, acc)), None

    init = (jnp.full((B, H, T, 1), -1e30, jnp.float32),
            jnp.zeros((B, H, T, 1), jnp.float32),
            jnp.zeros((B, H, T, D), jnp.float32))
    (_, l, acc), _ = jax.lax.scan(one_page, init,
                                  (jnp.arange(T // page), kp, vp))
    return jnp.moveaxis(acc / jnp.maximum(l, 1e-30), 1, 2).astype(q.dtype)


def attention(q, k, v, decode=None):
    """Causal GQA. q: (B, T, Hq, D); k, v: (B, T, Hkv, D). ``decode``:
    (page, first): rows from position ``first`` on were served by decode
    steps over a cache of ``page``-token pages, and attend as those do."""
    B, T, Hq, D = q.shape
    G = Hq // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    if T <= Q_BLOCK or T % Q_BLOCK:
        out = _attend(q, k, v, 0)
    else:
        qb = q.reshape(B, T // Q_BLOCK, Q_BLOCK, Hq, D)
        out = jax.lax.map(
            lambda a: _attend(a[0], k, v, a[1] * Q_BLOCK),
            (jnp.moveaxis(qb, 1, 0), jnp.arange(T // Q_BLOCK)))
        out = jnp.moveaxis(out, 0, 1).reshape(B, T, Hq, D)
    if decode is not None:
        page, first = decode
        served = (jnp.arange(T) >= first)[None, :, None, None]
        out = jnp.where(served, _attend_decode(q, k, v, page), out)
    return out.reshape(B, T, Hq * D)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x * x * x)))


def block(cfg, p, lora, x, positions, decode=None):
    s = cfg["lora_alpha"] / cfg["lora_rank"] if lora is not None else 0.0
    B, T, _ = x.shape
    hd = cfg["head_dim"]
    h = rmsnorm(x, p["norm1"], cfg["norm_eps"])
    q = linear(cfg, h, p, "wq", lora, s).reshape(B, T, cfg["num_heads"], hd)
    k = linear(cfg, h, p, "wk", lora, s).reshape(B, T, cfg["num_kv_heads"], hd)
    v = linear(cfg, h, p, "wv", lora, s).reshape(B, T, cfg["num_kv_heads"], hd)
    q = rope(q, positions, cfg["rope_theta"])
    k = rope(k, positions, cfg["rope_theta"])
    x = x + linear(cfg, attention(q, k, v, decode), p, "wo", lora, s)
    h = rmsnorm(x, p["norm2"], cfg["norm_eps"])
    if cfg["mlp_act"] == "swiglu":
        g = linear(cfg, h, p, "gate", lora, s)
        m = jax.nn.sigmoid(g) * g * linear(cfg, h, p, "up", lora, s)
    else:
        m = gelu_tanh(linear(cfg, h, p, "up", lora, s))
    return x + linear(cfg, m, p, "down", lora, s)


def client_half(cfg, W, lora, tokens, dtype, decode=None):
    """tokens (B, T) -> boundary activations (B, T, d)."""
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    x = W["embed"].astype(dtype)[tokens]
    for i in range(cfg["cut"]):
        x = block(cfg, W["layers"][i], None if lora is None
                  else lora.get(f"L{i}"), x, pos, decode)
    return x


def server_half(cfg, W, lora, x, decode=None):
    """boundary activations (B, T, d) -> logits (B, T, V) in float32."""
    B, T, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    for i in range(cfg["cut"], cfg["num_layers"]):
        x = block(cfg, W["layers"][i], None if lora is None
                  else lora.get(f"L{i}"), x, pos, decode)
    x = rmsnorm(x, W["final_norm"], cfg["norm_eps"])
    return jnp.matmul(x, W["head"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def cross_entropy(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


# ------------------------------------------------------------- training
def _sfl_ga_boundary():
    """Forward: each client's activations cross the int8 uplink with its
    own seed. Backward: the w-weighted sum of the clients' cotangents
    crosses the int8 downlink once and is broadcast to every client."""

    @jax.custom_vjp
    def f(x, w, seed):
        k = x.shape[0]
        seeds = jnp.asarray(seed, jnp.uint32) + \
            jnp.arange(k, dtype=jnp.uint32) * jnp.uint32(GOLDEN)
        return jax.vmap(lambda xi, si: int8_roundtrip(
            xi.reshape(-1, x.shape[-1]), si).reshape(xi.shape))(x, seeds)

    def fwd(x, w, seed):
        return f(x, w, seed), (w, seed)

    def bwd(res, g):
        w, seed = res
        agg = jnp.sum(g.astype(jnp.float32)
                      * w.reshape((-1,) + (1,) * (g.ndim - 1)), axis=0)
        down = jnp.asarray(seed, jnp.uint32) ^ jnp.uint32(DOWNLINK_MIX)
        agg = int8_roundtrip(agg.reshape(-1, g.shape[-1]), down
                             ).reshape(agg.shape)
        return (jnp.broadcast_to(agg[None], g.shape).astype(g.dtype),
                jnp.zeros_like(w), np.zeros((), jax.dtypes.float0))

    f.defvjp(fwd, bwd)
    return f


def _train_loss(cfg, dtype, trainable, W, batch):
    """Mean token cross-entropy of one sfl_ga step over the cohort."""
    idx = batch["idx"]
    client = jax.tree.map(lambda a: a[idx], trainable["client"])
    tokens, labels = batch["tokens"], batch["labels"]  # (K, b, S)
    K, b, S = tokens.shape
    smashed = jax.vmap(lambda lo, t: client_half(cfg, W, lo, t, dtype))(
        client, tokens)
    smashed = _sfl_ga_boundary()(smashed, batch["w"], batch["seed"])
    logits = server_half(cfg, W, trainable["server"],
                         smashed.reshape(K * b, S, -1))
    return cross_entropy(logits, labels.reshape(K * b, S))


def train_step(cfg, W, trainable, batch, lr, dtype=jnp.float32):
    """One SGD step of the cohort in ``batch``; pure, for the caller's jit.

    ``trainable``: {"client": {"L0": {"wq": {"a": (N, d, r), "b": ...}}},
    "server": {"L1": ...}}; ``batch``: tokens/labels (K, b, S) int32, idx
    (K,) bank rows, w (K,) aggregation weights, seed uint32.
    The parameters stay float32 whatever ``dtype`` is.
    Returns (loss, gradient, parameters after the step)."""
    loss, g = jax.value_and_grad(
        lambda t: _train_loss(cfg, dtype, t, W, batch))(trainable)
    new = jax.tree.map(lambda p, gi: p - lr * gi, trainable, g)
    return loss, g, new


# -------------------------------------------------------------- serving
def serve_logits(cfg, W, tokens, seeds, offsets, first, dtype=jnp.float32):
    """Logits at every position of one served sequence.

    ``tokens`` (T,): prompt then served tokens (padding past the end
    changes nothing before it: attention is causal). ``seeds``/``offsets``
    (T,): the wire seed of each position's boundary activation and the flat
    index of its first element in the payload that carried it. ``first``:
    the prompt's length; later positions were served by decode steps over
    a cache of ``cfg["page_size"]``-token pages."""
    decode = (cfg["page_size"], first)
    x = client_half(cfg, W, None, tokens[None], dtype, decode)[0]
    x = int8_roundtrip(x, seeds, offsets)
    return server_half(cfg, W, None, x[None], decode)[0]
