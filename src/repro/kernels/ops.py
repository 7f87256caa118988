"""jit'd public wrappers around the Pallas kernels.

``backend`` selection: "pallas" runs the kernel (compiled on a TPU,
interpreted on other backends — ``kernels.platform``); "jnp" runs the
oracle.
Model code calls these, so swapping a kernel in/out is a config flag.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.grad_agg import grad_agg_reduce
from repro.kernels.paged_attention import paged_attention_decode
from repro.kernels.quantize import dequant_agg_reduce, quantize_pack
from repro.kernels.ssd_scan import ssd_intra_chunk


def flash_attention(q, k, v, causal: bool = True, window: Optional[int] = None,
                    backend: str = "pallas", block_q: int = 128,
                    block_k: int = 128):
    """q: (B, S, Hq, D), k/v: (B, T, Hkv, D) — model layout (BSHD)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if backend == "jnp":
        out = ref.sdpa_ref(qt, kt, vt, causal, window)
    else:
        D = q.shape[-1]
        if D not in (64, 128):  # pad head_dim to the MXU lane width
            pad = 128 - D
            scale_fix = jnp.sqrt((D + pad) / D).astype(qt.dtype)
            qt = jnp.pad(qt, ((0, 0), (0, 0), (0, 0), (0, pad))) * scale_fix
            kt = jnp.pad(kt, ((0, 0), (0, 0), (0, 0), (0, pad)))
            vt = jnp.pad(vt, ((0, 0), (0, 0), (0, 0), (0, pad)))
            out = flash_attention_bhsd(qt, kt, vt, causal, window,
                                       block_q, block_k)[..., :D]
        else:
            out = flash_attention_bhsd(qt, kt, vt, causal, window,
                                       block_q, block_k)
    return jnp.swapaxes(out, 1, 2)


def paged_attention(q, pages_k, pages_v, page_table, lengths,
                    backend: str = "pallas"):
    """Batched single-token decode over a paged KV cache.

    q: (slots, Hq, D) — one query token per slot, model head layout;
    pages_k/pages_v: (Hkv, num_pages, page_size, D) physical pools;
    page_table: (slots, max_pages) int32; lengths: (slots,) int32
    including the just-written token. Returns (slots, Hq, D).

    The kernel wants GQA group-major q (slots, Hkv, G, D); G is padded to
    the f32 sublane width (8) so each grid step's q block is a legal VMEM
    tile. The padding happens BEFORE the backend branch — both the kernel
    and the oracle see the same padded shapes, so the per-row reduction
    order matches and bitwise parity survives (matmul bitwise results can
    legitimately depend on the M dimension).
    """
    slots, Hq, D = q.shape
    Hkv = pages_k.shape[0]
    G = Hq // Hkv
    Gp = G if G % 8 == 0 else G + 8 - G % 8
    qg = q.reshape(slots, Hkv, G, D)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    if backend == "jnp":
        out = ref.paged_attention_ref(qg, pages_k, pages_v,
                                      page_table, lengths)
    else:
        if D not in (64, 128):
            raise NotImplementedError(
                f"paged_attention pallas backend needs head_dim in "
                f"(64, 128), got {D}; use backend='jnp'")
        out = paged_attention_decode(qg, pages_k, pages_v, page_table,
                                     lengths)
    return out[:, :, :G].reshape(slots, Hq, D)


def ssd(x, dt, A, B, C, chunk: int, initial_state=None, backend: str = "pallas"):
    """Full SSD: Pallas intra-chunk kernel + jnp inter-chunk recurrence.

    Shapes as in repro.models.ssm.ssd_chunked.
    Returns (y (b,s,h,p), final_state (b,h,p,n)).
    """
    if backend == "jnp":
        return ref.ssd_ref(x, dt, A, B, C, chunk, initial_state)
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0
    c = s // chunk
    rep = h // g
    dtf = dt.astype(jnp.float32)
    xdt = (x.astype(jnp.float32) * dtf[..., None])
    dA = dtf * A.astype(jnp.float32)  # (b,s,h)
    Bf = jnp.repeat(B.astype(jnp.float32), rep, axis=2)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=2)

    # -> (b, h, c, Q, ...)
    xdt_c = xdt.reshape(b, c, chunk, h, p).transpose(0, 3, 1, 2, 4)
    dA_c = dA.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)
    B_c = Bf.reshape(b, c, chunk, h, n).transpose(0, 3, 1, 2, 4)
    C_c = Cf.reshape(b, c, chunk, h, n).transpose(0, 3, 1, 2, 4)

    y_diag, states = ssd_intra_chunk(xdt_c, dA_c, B_c, C_c)

    # inter-chunk recurrence (jnp; c is small)
    A_cs = jnp.cumsum(dA_c, axis=-1)  # (b,h,c,Q)
    chunk_sum = A_cs[..., -1]  # (b,h,c)
    init = (jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None
            else initial_state.astype(jnp.float32))

    def step(carry, inp):
        st_c, decay_c = inp  # (b,h,p,n), (b,h)
        prev = carry
        new = prev * jnp.exp(decay_c)[..., None, None] + st_c
        return new, prev

    st_seq = jnp.moveaxis(states, 2, 0)  # (c,b,h,p,n)
    dc_seq = jnp.moveaxis(chunk_sum, 2, 0)  # (c,b,h)
    final, prevs = jax.lax.scan(step, init, (st_seq, dc_seq))
    prev_states = jnp.moveaxis(prevs, 0, 2)  # (b,h,c,p,n)

    # off-diagonal: y_off[q] = C[q] @ prev_state * exp(A_cs[q])
    y_off = jnp.einsum("bhcqn,bhcpn,bhcq->bhcqp", C_c, prev_states,
                       jnp.exp(A_cs))
    y = (y_diag.astype(jnp.float32) + y_off)
    y = y.transpose(0, 2, 3, 1, 4).reshape(b, s, h, p)
    return y.astype(x.dtype), final


def grad_agg(g, rho, backend: str = "pallas"):
    """Σ_n ρ_n g_n over the client axis. g: (N, T, D) or (N, B, S, D)."""
    shape = g.shape
    if g.ndim == 4:
        g = g.reshape(shape[0], shape[1] * shape[2], shape[3])
    if backend == "jnp":
        out = ref.grad_agg_ref(g, rho)
    else:
        out = grad_agg_reduce(g, rho)
    if len(shape) == 4:
        out = out.reshape(shape[1], shape[2], shape[3])
    return out


def quantize(g, seed=0, bits: int = 8, backend: str = "pallas",
             block_t: int = 256, block_d: int = 256,
             stochastic: bool = True):
    """Per-client per-tile symmetric quantization of (N, T, D) or
    (N, B, S, D) smashed data. Returns (q int8 payload, scales f32);
    ``bits=4`` packs two values per int8 word. The (block_t, block_d)
    tiling is the on-wire scale granularity — both backends and the
    matching ``dequant_agg`` must use the same one."""
    shape = g.shape
    if g.ndim == 4:
        g = g.reshape(shape[0], shape[1] * shape[2], shape[3])
    if backend == "jnp":
        return ref.quantize_ref(g, seed, bits, block_t, block_d, stochastic)
    return quantize_pack(g, seed, bits, block_t, block_d, stochastic)


def dequant_agg(q, scales, rho, bits: int = 8, backend: str = "pallas",
                block_t: int = 256, block_d: int = 256):
    """Fused decode + eq. 5 reduce of N quantized payloads: the server-side
    endpoint of the compressed gradient-aggregation path. Returns (T, D)."""
    if backend == "jnp":
        return ref.dequant_agg_ref(q, scales, rho, bits, block_t, block_d)
    return dequant_agg_reduce(q, scales, rho, bits, block_t, block_d)
