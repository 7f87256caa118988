"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sdpa_ref(q, k, v, causal: bool = True, window=None):
    """q: (B, Hq, S, D); k, v: (B, Hkv, T, D). Plain softmax attention."""
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    g = Hq // Hkv
    kf = jnp.repeat(k.astype(jnp.float32), g, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) / jnp.sqrt(D)
    qi = jnp.arange(S)[:, None]
    kj = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kj <= qi
    if window is not None:
        mask &= kj > qi - window
    s = jnp.where(mask[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, vf).astype(q.dtype)


def ssd_ref(x, dt, A, B, C, chunk: int, initial_state=None):
    """Chunked-SSD oracle — delegates to the model's reference impl."""
    from repro.models.ssm import ssd_chunked

    return ssd_chunked(x, dt, A, B, C, chunk, initial_state)


def ssd_sequential_ref(x, dt, A, B, C, initial_state=None):
    """O(S) recurrent oracle (validates the chunked algorithm itself)."""
    from repro.models.ssm import ssd_step

    b, s, h, p = x.shape
    n = B.shape[-1]
    state = (jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None
             else initial_state.astype(jnp.float32))

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp
        y, state = ssd_step(state, x_t, dt_t, A, B_t, C_t)
        return state, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(B, 1, 0), jnp.moveaxis(C, 1, 0))
    state, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1), state


def paged_attention_ref(q, pages_k, pages_v, page_table, lengths):
    """Oracle for kernels.paged_attention.paged_attention_decode —
    bit-identical output: the same f32 online-softmax update sequence,
    page by page in grid order, with the same page-skip predicate (a
    fully-masked page leaves the running state untouched, exactly like
    the kernel's ``pl.when``; a dead slot — length 0 — yields zeros).

    q: (slots, Hkv, G, D); pools: (Hkv, P, page, D); page_table:
    (slots, max_pages) int32; lengths: (slots,) int32.
    """
    import math

    B, Hkv, G, D = q.shape
    maxp = page_table.shape[1]
    page = pages_k.shape[2]
    scale = 1.0 / math.sqrt(D)
    NEG_INF = -1e30
    kg = jnp.moveaxis(pages_k[:, page_table], 0, 1)  # (B, Hkv, maxp, page, D)
    vg = jnp.moveaxis(pages_v[:, page_table], 0, 1)

    def one_head(qbh, kpages, vpages, length):
        qf = qbh.astype(jnp.float32) * scale            # (G, D)

        def step(carry, inp):
            m, l, acc = carry
            j, k, v = inp
            s = jax.lax.dot_general(qf, k.astype(jnp.float32),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(p, v.astype(jnp.float32),
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_new = acc * alpha + pv
            hit = j * page < length  # the kernel's pl.when page skip
            return (jnp.where(hit, m_new, m), jnp.where(hit, l_new, l),
                    jnp.where(hit, acc_new, acc)), None

        init = (jnp.full((G, 1), NEG_INF, jnp.float32),
                jnp.zeros((G, 1), jnp.float32),
                jnp.zeros((G, D), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            step, init, (jnp.arange(maxp, dtype=jnp.int32), kpages, vpages))
        return (acc / jnp.maximum(l, 1e-30)).astype(qbh.dtype)

    per_slot = jax.vmap(one_head, in_axes=(0, 0, 0, None))  # over Hkv
    return jax.vmap(per_slot)(q, kg, vg, lengths)           # over slots


def grad_agg_ref(g, rho):
    """out = Σ_n ρ_n g_n. g: (N, T, D); rho: (N,)."""
    return jnp.einsum("ntd,n->td", g.astype(jnp.float32),
                      rho.astype(jnp.float32)).astype(g.dtype)


def _tile_scales(g, block_t, block_d, qmax):
    """Per-(client, tile) symmetric scales, (N, T/bt, D/bd) — the wire
    format shared with kernels.quantize."""
    N, T, D = g.shape
    gt = jnp.abs(g.astype(jnp.float32)).reshape(
        N, T // block_t, block_t, D // block_d, block_d)
    absmax = jnp.max(gt, axis=(2, 4))  # (N, Tt, Dt)
    # constant-reciprocal multiply, matching the kernel bit-for-bit (a
    # constant divide is strength-reduced inconsistently by XLA)
    return jnp.where(absmax > 0.0, absmax * (1.0 / qmax), 1.0)


def _expand_scales(scales, block_t, block_d):
    """(N, Tt, Dt) -> (N, T, D) by tile repetition."""
    return jnp.repeat(jnp.repeat(scales, block_t, axis=1), block_d, axis=2)


def quantize_ref(g, seed=0, bits: int = 8, block_t: int = 256,
                 block_d: int = 256, stochastic: bool = True):
    """Oracle for kernels.quantize.quantize_pack — bit-identical output
    (same global-coordinate hash, same tile semantics, same packing)."""
    from repro.kernels.quantize import hash_uniform, qmax_for

    N, T, D = g.shape
    block_t = min(block_t, T)
    block_d = min(block_d, D)
    qmax = qmax_for(bits)
    scales = _tile_scales(g, block_t, block_d, qmax)
    s_full = _expand_scales(scales, block_t, block_d)
    if stochastic:
        n = jax.lax.broadcasted_iota(jnp.uint32, (N, T, D), 0)
        t = jax.lax.broadcasted_iota(jnp.uint32, (N, T, D), 1)
        d = jax.lax.broadcasted_iota(jnp.uint32, (N, T, D), 2)
        u = hash_uniform(n, t, d, seed)
    else:
        u = 0.5
    q = jnp.clip(jnp.floor(g.astype(jnp.float32) / s_full + u),
                 -qmax, qmax).astype(jnp.int32)
    if bits == 4:
        # each tile row's first half in the low nibbles, second in the high
        h = block_d // 2
        q = q.reshape(N, T, D // block_d, block_d)
        q = ((q[..., h:] & 15) << 4) | (q[..., :h] & 15)
        q = q.reshape(N, T, D // 2)
    return q.astype(jnp.int8), scales


def unpack_int4_ref(q, block_d: int = 256):
    """(N, T, D/2) int4 payload words -> (N, T, D) int32 codes: each tile
    row's low nibbles are its first half, the high nibbles its second."""
    N, T, Dq = q.shape
    bd = min(block_d, 2 * Dq)
    w = q.astype(jnp.int32).reshape(N, T, 2 * Dq // bd, bd // 2)
    # sign-extend each nibble by shifting it to the top of the int32
    qi = jnp.concatenate([(w << 28) >> 28, (w << 24) >> 28], axis=-1)
    return qi.reshape(N, T, 2 * Dq)


def dequant_agg_ref(q, scales, rho, bits: int = 8, block_t: int = 256,
                    block_d: int = 256):
    """Oracle for kernels.quantize.dequant_agg_reduce: unpack, rescale and
    ρ-reduce N payloads. Returns (T, D) f32."""
    qi = unpack_int4_ref(q, block_d) if bits == 4 else q.astype(jnp.int32)
    N, T, D = qi.shape
    block_t = min(block_t, T)
    block_d = min(block_d, D)
    s_full = _expand_scales(scales, block_t, block_d)
    g = qi.astype(jnp.float32) * s_full
    return jnp.einsum("ntd,n->td", g, rho.astype(jnp.float32))
