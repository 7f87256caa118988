"""Plain reference of a Granite 4.0-H hybrid LM split at a cut, with the
int8 wire: Mamba-2 and NoPE GQA layers, a SwiGLU MLP in each, and the
Granite multipliers.

Written from the published equations, in straightforward ``jax.numpy``,
and read from the published configuration's own keys (``config.json`` of
``granitemoehybrid``: ``layer_types``, ``mamba_*``, the multipliers), not
from the program's. Nothing is imported from the system under test; the
int8 wire, the sfl_ga boundary, the cross-entropy, RMSNorm, the LoRA
linear, RoPE and causal GQA attention are ``dense_split_lm``'s. It serves
``train_step``: sfl_ga split LoRA training with SGD, step by step, with
the interface of ``dense_split_lm.train_step``.

The layer (pre-RMSNorm both times):

    x = embed(tokens) * embedding_multiplier
    x = x + residual_multiplier * mixer(rmsnorm(x))
    x = x + residual_multiplier * swiglu(rmsnorm(x))
    logits = head(rmsnorm(x)) / logits_scaling

with ``mixer`` attention at the layers ``layer_types`` names so, Mamba-2
at the others. Attention: GQA, scores ``q.k * attention_multiplier``
(1/sqrt(head_dim) where it is None), no positional encoding where
``position_embedding_type`` is ``"nope"`` (rotate-half RoPE otherwise).
Mamba-2 (arXiv:2405.21060, §3 and §7): ``in_proj`` (no bias) to z, x B C
and dt; a depthwise causal conv with bias over x B C, then SiLU;
``dt = softplus(dt_raw + dt_bias)`` without a clamp; ``A = -exp(A_log)``;
the SSD in its unchunked quadratic form, per sequence and head

    y = (L o C B^T) (dt . x) + D x,   L[i, j] = exp(sum_{j<m<=i} dt_m A)

for j <= i and 0 above the diagonal; the gated RMSNorm
``rmsnorm(y * silu(z))`` over the whole inner width (one group); then
``out_proj`` (no bias). LoRA adapts the attention projections, the
Mamba-2 ``in_proj`` and ``out_proj``, and the MLP's.

Precision: ``dtype`` is the activation type, as in ``dense_split_lm``,
and so is the matmul rule of the projections and of attention: float32
at the chip's default matmul precision (on a TPU one bfloat16 pass with
float32 accumulation), bfloat16 activations for the control. The rule
is the same because the program's float32 runs its projections at that
default, and rounds their operands where this reference does. The SSD
is the exception: the program computes its chunked SSD and its conv in
float32 (``HIGHEST``), so here the quadratic form's products are exact
float32 too; at the default, each side would round the decays at other
points, and that alone reads as large as the bfloat16 control. Norm
statistics, the softmax, the conv, dt, the decays ``L`` and the
cross-entropy stay in float32 in both; the control's SSD takes its
operands in bfloat16. Each layer is rematerialized in the backward pass,
so that the (rows, heads, T, T) decays of one layer at a time live
beside the weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import dense_split_lm as dense


HIGHEST = jax.lax.Precision.HIGHEST


def _silu(x):
    return jax.nn.sigmoid(x) * x


def causal_conv(x, w, b):
    """Depthwise causal conv: out[t] = b + sum_k w[k] x[t - (K-1) + k],
    zero before the first position. x: (B, T, CH); w: (K, CH)."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    out = b.astype(jnp.float32) + sum(
        w[k].astype(jnp.float32) * xp[:, k:k + T] for k in range(K))
    return out.astype(x.dtype)


def ssd(x, dt, A, B, C):
    """The SSD's quadratic form over whole sequences. x: (b, T, H, P);
    dt: (b, T, H) float32; A: (H,); B, C: (b, T, G, N)."""
    b, T, H, P = x.shape
    G = B.shape[2]
    dA = jnp.moveaxis(dt * A, 1, 2)  # (b, H, T)
    # seg[i, j] = sum of dA[m] over j < m <= i: column j sums its own terms
    # from row j + 1 down, so no difference of long prefix sums is taken
    below = jnp.tril(jnp.ones((T, T), bool), -1)  # [m, j]: m > j
    seg = jnp.cumsum(jnp.where(below, dA[..., :, None], 0.0), axis=-2)
    L = jnp.where(jnp.tril(jnp.ones((T, T), bool)), jnp.exp(seg), 0.0)
    CB = jnp.einsum("bign,bjgn->bgij", C, B, precision=HIGHEST,
                    preferred_element_type=jnp.float32)
    M = L.reshape(b, G, H // G, T, T) * CB[:, :, None]
    xdt = x * dt[..., None].astype(x.dtype)
    y = jnp.einsum("bghij,bjghp->bighp", M.astype(x.dtype),
                   xdt.reshape(b, T, G, H // G, P), precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    return y.reshape(b, T, H, P).astype(x.dtype)


def mamba2(cfg, p, lora, s, x):
    B, T, _ = x.shape
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    di, gn = H * P, G * N
    zxbcdt = dense.linear(cfg, x, p, "ssm_in_proj", lora, s)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * gn],
                  zxbcdt[..., 2 * di + 2 * gn:])
    xbc = _silu(causal_conv(xbc, p["ssm_conv_w"], p["ssm_conv_b"]))
    xs = xbc[..., :di].reshape(B, T, H, P)
    Bm = xbc[..., di:di + gn].reshape(B, T, G, N)
    Cm = xbc[..., di + gn:].reshape(B, T, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["ssm_dt_bias"])
    A = -jnp.exp(p["ssm_A_log"].astype(jnp.float32))
    y = ssd(xs, dt, A, Bm, Cm) + p["ssm_D"].astype(x.dtype)[:, None] * xs
    y = dense.rmsnorm(y.reshape(B, T, di) * _silu(z), p["ssm_norm"],
                      cfg["rms_norm_eps"])
    return dense.linear(cfg, y, p, "ssm_out_proj", lora, s)


def attention(cfg, p, lora, s, x, positions):
    B, T, _ = x.shape
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    q = dense.linear(cfg, x, p, "wq", lora, s).reshape(
        B, T, cfg["num_attention_heads"], hd)
    k = dense.linear(cfg, x, p, "wk", lora, s).reshape(
        B, T, cfg["num_key_value_heads"], hd)
    v = dense.linear(cfg, x, p, "wv", lora, s).reshape(
        B, T, cfg["num_key_value_heads"], hd)
    if cfg["position_embedding_type"] != "nope":
        q = dense.rope(q, positions, cfg["rope_theta"])
        k = dense.rope(k, positions, cfg["rope_theta"])
    if cfg["attention_multiplier"] is not None:
        # dense.attention divides the scores by sqrt(head_dim)
        q = q * (cfg["attention_multiplier"] * math.sqrt(hd))
    return dense.linear(cfg, dense.attention(q, k, v), p, "wo", lora, s)


def block(cfg, kind, p, lora, x, positions):
    s = cfg["lora_alpha"] / cfg["lora_rank"] if lora is not None else 0.0
    r = cfg["residual_multiplier"]
    eps = cfg["rms_norm_eps"]
    h = dense.rmsnorm(x, p["norm1"], eps)
    if kind == "attention":
        x = x + r * attention(cfg, p, lora, s, h, positions)
    else:
        x = x + r * mamba2(cfg, p, lora, s, h)
    h = dense.rmsnorm(x, p["norm2"], eps)
    g = dense.linear(cfg, h, p, "gate", lora, s)
    m = _silu(g) * dense.linear(cfg, h, p, "up", lora, s)
    return x + r * dense.linear(cfg, m, p, "down", lora, s)


def _layers(cfg, W, lora, x, first, last):
    B, T, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    for i in range(first, last):
        kind = cfg["layer_types"][i]
        x = jax.checkpoint(lambda p, lo, x, kind=kind: block(
            cfg, kind, p, lo, x, pos))(
            W["layers"][i], None if lora is None else lora.get(f"L{i}"), x)
    return x


def client_half(cfg, W, lora, tokens, dtype):
    """tokens (B, T) -> boundary activations (B, T, d)."""
    x = W["embed"].astype(dtype)[tokens] * cfg["embedding_multiplier"]
    return _layers(cfg, W, lora, x, 0, cfg["cut"])


def server_half(cfg, W, lora, x):
    """boundary activations (B, T, d) -> logits (B, T, V) in float32."""
    x = _layers(cfg, W, lora, x, cfg["cut"], len(cfg["layer_types"]))
    x = dense.rmsnorm(x, W["final_norm"], cfg["rms_norm_eps"])
    logits = jnp.matmul(x, W["head"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return logits / cfg["logits_scaling"]


# ------------------------------------------------------------- training
def _train_loss(cfg, dtype, trainable, W, batch):
    """Mean token cross-entropy of one sfl_ga step over the cohort."""
    client = jax.tree.map(lambda a: a[batch["idx"]], trainable["client"])
    tokens, labels = batch["tokens"], batch["labels"]  # (K, b, S)
    K, b, S = tokens.shape
    smashed = jax.vmap(lambda lo, t: client_half(cfg, W, lo, t, dtype))(
        client, tokens)
    smashed = dense._sfl_ga_boundary()(smashed, batch["w"], batch["seed"])
    logits = server_half(cfg, W, trainable["server"],
                         smashed.reshape(K * b, S, -1))
    return dense.cross_entropy(logits, labels.reshape(K * b, S))


def train_step(cfg, W, trainable, batch, lr, dtype=jnp.float32):
    """One SGD step of the cohort in ``batch``, as
    ``dense_split_lm.train_step``. Returns (loss, gradient, parameters
    after the step)."""
    loss, g = jax.value_and_grad(
        lambda t: _train_loss(cfg, dtype, t, W, batch))(trainable)
    new = jax.tree.map(lambda p, gi: p - lr * gi, trainable, g)
    return loss, g, new
