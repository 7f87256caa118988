"""Mamba-2 block (state-space duality, arXiv:2405.21060).

``ssd_chunked`` is the pure-jnp reference for the chunked SSD algorithm
(intra-chunk dual/quadratic form + inter-chunk state recurrence). The Pallas
kernel in repro.kernels.ssd_scan targets the intra-chunk term and is
validated against this function.

Decode is O(1) per token: a single recurrent state update — this is why
SSM/hybrid architectures run the long_500k shape natively.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.blocks import init_linear, init_rmsnorm, linear, rmsnorm


# The SSD and the conv compute in float32 whatever the model's dtype. At
# the chip's default precision a float32 matmul rounds its operands to
# bfloat16, the decays exp(segsum) and the chunk states among them.
F32 = jax.lax.Precision.HIGHEST


class SSMCache(NamedTuple):
    conv: jnp.ndarray  # (B, k-1, conv_channels) last raw inputs
    state: jnp.ndarray  # (B, H, P, N)
    length: jnp.ndarray  # () int32


def segsum(x: jnp.ndarray) -> jnp.ndarray:
    """(..., q) -> (..., q, q): out[i, j] = sum_{j < m <= i} x[m]; -inf above diag."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, d, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int,
                initial_state=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD. x:(b,s,h,p) dt:(b,s,h) A:(h,) B,C:(b,s,g,n).

    Returns (y (b,s,h,p), final_state (b,h,p,n)). All math in fp32.

    B and C stay in group form: the heads are viewed as (g, r = h/g), head
    ``j`` reading group ``j // r``, so ``C B^T`` is formed once a group and
    chunk and neither B nor C is repeated over heads. With ``g == h`` this
    is the per-head computation. Each contraction is a pairwise einsum.
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0, (s, chunk)
    c = s // chunk
    r = h // g
    dtf = dt.astype(jnp.float32)
    xdt = x.astype(jnp.float32) * dtf[..., None]  # fold dt into x
    dA = dtf * A.astype(jnp.float32)  # (b,s,h) log-decay

    # -> chunked views, heads as (g, r)
    xc = xdt.reshape(b, c, chunk, g, r, p)
    Bc = B.astype(jnp.float32).reshape(b, c, chunk, g, n)
    Cc = C.astype(jnp.float32).reshape(b, c, chunk, g, n)
    Ac = dA.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)  # (b,h,c,q)
    A_cs = jnp.cumsum(Ac, axis=-1)  # (b,h,c,q)

    # 1) intra-chunk (dual quadratic form): C B^T once a group, then the
    # per-head decays, then the values
    CB = jnp.einsum("bclgn,bcsgn->bgcls", Cc, Bc, precision=F32)
    L = jnp.exp(segsum(Ac)).reshape(b, g, r, c, chunk, chunk)
    y_diag = jnp.einsum("bgrcls,bcsgrp->bclgrp", L * CB[:, :, None], xc,
                        precision=F32)

    # 2) chunk-final states
    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)  # (b,h,c,q)
    x_decayed = xc * decay_states.reshape(b, g, r, c, chunk).transpose(
        0, 3, 4, 1, 2)[..., None]
    states = jnp.einsum("bclgn,bclgrp->bcgrpn", Bc, x_decayed,
                        precision=F32).reshape(b, c, h, p, n)

    # 3) inter-chunk recurrence via the chunk-level decay matrix
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)
    states = jnp.concatenate([initial_state[:, None].astype(jnp.float32), states], 1)
    chunk_decay = jnp.exp(segsum(jnp.pad(A_cs[..., -1], ((0, 0), (0, 0), (1, 0)))))
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states,
                            precision=F32)
    prev_states, final_state = new_states[:, :-1], new_states[:, -1]

    # 4) inter-chunk contribution to outputs
    state_decay_out = jnp.exp(A_cs).reshape(b, g, r, c, chunk).transpose(
        0, 3, 4, 1, 2)  # (b,c,q,g,r)
    y_off = jnp.einsum("bclgn,bcgrpn->bclgrp", Cc,
                       prev_states.reshape(b, c, g, r, p, n),
                       precision=F32) * state_decay_out[..., None]

    y = (y_diag + y_off).reshape(b, s, h, p)
    return y.astype(x.dtype), final_state


def ssd_step(state, x_t, dt_t, A, B_t, C_t) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single recurrent step. state:(b,h,p,n) x_t:(b,h,p) dt_t:(b,h) B_t,C_t:(b,g,n)."""
    h = x_t.shape[1]
    rep = h // B_t.shape[1]
    Bf = jnp.repeat(B_t.astype(jnp.float32), rep, axis=1)  # (b,h,n)
    Cf = jnp.repeat(C_t.astype(jnp.float32), rep, axis=1)
    dtf = dt_t.astype(jnp.float32)
    dA = jnp.exp(dtf * A.astype(jnp.float32))  # (b,h)
    upd = jnp.einsum("bhp,bhn->bhpn", x_t.astype(jnp.float32) * dtf[..., None], Bf,
                     precision=F32)
    new_state = state * dA[..., None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Cf, precision=F32)
    return y.astype(x_t.dtype), new_state


# ---------------------------------------------------------------------------
# Full Mamba-2 mixer block
# ---------------------------------------------------------------------------

def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    H = d_inner // s.head_dim
    conv_ch = d_inner + 2 * s.n_groups * s.state_dim
    return d_inner, H, conv_ch


def init_mamba2(key, cfg: ModelConfig, dtype=jnp.float32):
    s = cfg.ssm
    d_inner, H, conv_ch = _dims(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d_in_proj = 2 * d_inner + 2 * s.n_groups * s.state_dim + H
    dt = jnp.exp(jax.random.uniform(k3, (H,), jnp.float32)
                 * (math.log(s.dt_max) - math.log(s.dt_min)) + math.log(s.dt_min))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    return {
        "in_proj": init_linear(k1, cfg.d_model, d_in_proj, False, dtype),
        "conv_w": (jax.random.normal(k2, (s.conv_dim, conv_ch), jnp.float32)
                   / math.sqrt(s.conv_dim)).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt_bias,
        "norm": init_rmsnorm(d_inner, dtype),
        "out_proj": init_linear(k4, d_inner, cfg.d_model, False, dtype),
    }


def _split_in_proj(cfg: ModelConfig, zxbcdt):
    s = cfg.ssm
    d_inner, H, _ = _dims(cfg)
    gn = s.n_groups * s.state_dim
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    return z, xBC, dt


def _causal_conv(w, b, x):
    """Depthwise causal conv. x: (B, S, CH); w: (k, CH)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp.astype(jnp.float32),
        w.astype(jnp.float32)[:, None, :],  # (k, 1, CH) w/ dim numbers below
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1],
        precision=F32,
    )
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def _ssm_inner(cfg, params, xBC_conv, dt_raw, use_kernel: bool, prev_state=None):
    s = cfg.ssm
    d_inner, H, _ = _dims(cfg)
    gn = s.n_groups * s.state_dim
    B_sz = xBC_conv.shape[0]
    S = xBC_conv.shape[1]
    xs, Bm, Cm = jnp.split(xBC_conv, [d_inner, d_inner + gn], axis=-1)
    x = xs.reshape(B_sz, S, H, s.head_dim)
    Bmat = Bm.reshape(B_sz, S, s.n_groups, s.state_dim)
    Cmat = Cm.reshape(B_sz, S, s.n_groups, s.state_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    y, final_state = _ssd_any_length(x, dt, A, Bmat, Cmat, s.chunk_size,
                                     prev_state, use_kernel)
    y = y + params["D"].astype(y.dtype)[None, None, :, None] * x
    return y.reshape(B_sz, S, d_inner), final_state


def _ssd_tail_sequential(x, dt, A, B, C, state):
    """O(S) recurrent sweep (scan of ``ssd_step``) — the ragged tail of
    ``_ssd_any_length``. Same recurrence the decode path runs token by
    token, so a prefill at any length hands decode the exact state it
    would have reached itself."""
    b, _, h, p = x.shape
    n = B.shape[-1]
    if state is None:
        state = jnp.zeros((b, h, p, n), jnp.float32)

    def step(st, inp):
        x_t, dt_t, B_t, C_t = inp
        y, st = ssd_step(st, x_t, dt_t, A, B_t, C_t)
        return st, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(B, 1, 0), jnp.moveaxis(C, 1, 0))
    state, ys = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1), state


def _ssd_any_length(x, dt, A, B, C, chunk: int, prev_state, use_kernel: bool):
    """SSD over an arbitrary sequence length: the chunk-aligned head runs
    the chunked dual form (or the Pallas kernel), the remainder runs the
    sequential recurrence seeded with the head's final state. Serving
    prompts (exact-length prefill, DESIGN.md §18) are rarely multiples
    of the chunk size; training lengths still are, so the aligned path
    is byte-identical to before."""
    S = x.shape[1]
    s0 = (S // chunk) * chunk
    if s0 == S:
        if use_kernel:
            from repro.kernels import ops as kops
            return kops.ssd(x, dt, A, B, C, chunk=chunk,
                            initial_state=prev_state)
        return ssd_chunked(x, dt, A, B, C, chunk=chunk,
                           initial_state=prev_state)
    state = prev_state
    if s0:
        y_head, state = _ssd_any_length(x[:, :s0], dt[:, :s0], A, B[:, :s0],
                                        C[:, :s0], chunk, state, use_kernel)
    y_tail, state = _ssd_tail_sequential(x[:, s0:], dt[:, s0:], A, B[:, s0:],
                                         C[:, s0:], state)
    if s0:
        y_tail = jnp.concatenate([y_head, y_tail.astype(y_head.dtype)], axis=1)
    return y_tail.astype(x.dtype), state


def _mixer(params, cfg: ModelConfig, x, use_kernel: bool):
    """The Mamba-2 mixer over a whole sequence, under the named scopes
    ``ssm/in_proj``, ``ssm/conv``, ``ssm/ssd``, ``ssm/gate_norm`` and
    ``ssm/out_proj``. Returns (out, the conv's raw input, final state)."""
    with jax.named_scope("ssm/in_proj"):
        zxbcdt = linear(params["in_proj"], x)
    z, xBC_raw, dt_raw = _split_in_proj(cfg, zxbcdt)
    with jax.named_scope("ssm/conv"):
        xBC = jax.nn.silu(_causal_conv(params["conv_w"], params["conv_b"],
                                       xBC_raw))
    with jax.named_scope("ssm/ssd"):
        y, final_state = _ssm_inner(cfg, params, xBC, dt_raw, use_kernel)
    with jax.named_scope("ssm/gate_norm"):
        y = rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    with jax.named_scope("ssm/out_proj"):
        return linear(params["out_proj"], y), xBC_raw, final_state


def mamba2_train(params, cfg: ModelConfig, x, use_kernel: bool = False):
    """x: (B, S, d_model) -> (B, S, d_model)."""
    return _mixer(params, cfg, x, use_kernel)[0]


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype) -> SSMCache:
    s = cfg.ssm
    d_inner, H, conv_ch = _dims(cfg)
    return SSMCache(
        conv=jnp.zeros((batch, s.conv_dim - 1, conv_ch), dtype),
        state=jnp.zeros((batch, H, s.head_dim, s.state_dim), jnp.float32),
        length=jnp.zeros((), jnp.int32),
    )


def mamba2_prefill(params, cfg: ModelConfig, x, use_kernel: bool = False):
    s = cfg.ssm
    out, xBC_raw, final_state = _mixer(params, cfg, x, use_kernel)
    cache = SSMCache(
        conv=xBC_raw[:, -(s.conv_dim - 1):, :],
        state=final_state,
        length=jnp.asarray(x.shape[1], jnp.int32),
    )
    return out, cache


def mamba2_decode(params, cfg: ModelConfig, x, cache: SSMCache):
    """x: (B, 1, d_model). One recurrent step, under ``_mixer``'s scopes."""
    s = cfg.ssm
    d_inner, H, conv_ch = _dims(cfg)
    gn = s.n_groups * s.state_dim
    with jax.named_scope("ssm/in_proj"):
        zxbcdt = linear(params["in_proj"], x[:, 0, :])  # (B, ...)
    z, xBC_t, dt_raw = _split_in_proj(cfg, zxbcdt)
    # conv step over the last conv_dim inputs
    hist = jnp.concatenate([cache.conv, xBC_t[:, None, :]], axis=1)  # (B,k,CH)
    with jax.named_scope("ssm/conv"):
        w = params["conv_w"].astype(jnp.float32)  # (k, CH)
        xBC = jnp.einsum("bkc,kc->bc", hist.astype(jnp.float32), w,
                         precision=F32)
        xBC = jax.nn.silu(xBC + params["conv_b"].astype(jnp.float32)
                          ).astype(x.dtype)
    xs, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + gn], axis=-1)
    x_t = xs.reshape(-1, H, s.head_dim)
    B_t = Bm.reshape(-1, s.n_groups, s.state_dim)
    C_t = Cm.reshape(-1, s.n_groups, s.state_dim)
    with jax.named_scope("ssm/ssd"):
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
        A = -jnp.exp(params["A_log"])
        y_t, new_state = ssd_step(cache.state, x_t, dt, A, B_t, C_t)
        y_t = y_t + params["D"].astype(y_t.dtype)[None, :, None] * x_t
    y = y_t.reshape(-1, 1, d_inner)
    with jax.named_scope("ssm/gate_norm"):
        y = rmsnorm(params["norm"], y * jax.nn.silu(z[:, None, :]),
                    cfg.norm_eps)
    new_cache = SSMCache(conv=hist[:, 1:, :], state=new_state,
                         length=cache.length + 1)
    with jax.named_scope("ssm/out_proj"):
        return linear(params["out_proj"], y), new_cache


def ssm_flops_per_token(cfg: ModelConfig) -> int:
    s = cfg.ssm
    d_inner, H, conv_ch = _dims(cfg)
    gn = s.n_groups * s.state_dim
    f = 2 * cfg.d_model * (2 * d_inner + 2 * gn + H)  # in_proj
    f += 2 * conv_ch * s.conv_dim  # conv
    f += 2 * d_inner * s.state_dim * 2  # state update + output (per token amortized)
    f += 2 * d_inner * s.chunk_size * 2  # intra-chunk dual-form amortized
    f += 2 * d_inner * cfg.d_model  # out_proj
    return f
