"""Paged batched-decode attention (TPU Pallas): one query token per slot
gathered against that slot's page list — the repo's first inference-side
kernel (DESIGN.md §18).

Layout: q ``(slots, Hkv, G, D)`` (GQA group-major: the G query heads that
share one KV head form the MXU M-dimension), physical pools
``(Hkv, num_pages, page_size, D)``, page table ``(slots, max_pages)``
int32, lengths ``(slots,)`` int32.

The grid is ``(slots, Hkv, max_pages)`` with the page axis innermost and
sequential; the page table and lengths ride
``pltpu.PrefetchScalarGridSpec`` scalar prefetch, so the k/v BlockSpec
index maps dereference ``page_table[b, j]`` BEFORE the kernel body runs —
the DMA engine gathers exactly the pages a slot owns, never the dense
``slots × max_len`` rectangle. Online softmax (running max / denom / acc
in VMEM scratch, as in ``flash_attention``) accumulates across pages;
pages at or beyond a slot's length are skipped entirely (`pl.when`), so
the fully-masked-tile ``exp(0)`` poisoning cannot occur and retired slots
(length 0) produce exact zeros.

Bit parity: ``kernels.ref.paged_attention_ref`` replays the identical
f32 op sequence page by page; ``tests/test_paged_attention.py`` pins
bitwise equality in interpret mode for native head dims (64, 128).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

NEG_INF = -1e30


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, page_size: int,
                  num_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    # page j holds positions [j*page, (j+1)*page); skip it entirely when
    # the slot's context ends before it (includes length == 0 dead slots)
    @pl.when(j * page_size < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (page, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[...]                                # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                             # (G, page)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                # (page, D)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(j == num_pages - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_decode(q, pages_k, pages_v, page_table, lengths,
                           interpret: Optional[bool] = None):
    """q: (slots, Hkv, G, D); pools: (Hkv, P, page, D); table: (slots,
    max_pages) int32; lengths: (slots,) int32 INCLUDING the just-written
    query token. Returns (slots, Hkv, G, D)."""
    B, Hkv, G, D = q.shape
    num_pages, page = pages_k.shape[1], pages_k.shape[2]
    maxp = page_table.shape[1]
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_paged_kernel, scale=scale, page_size=page,
                               num_pages=maxp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, j, pt, ln: (h, pt[b, j], 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, j, pt, ln: (h, pt[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, j, pt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),  # running max
            pltpu.VMEM((G, 1), jnp.float32),  # running denom
            pltpu.VMEM((G, D), jnp.float32),  # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(page_table, lengths, q, pages_k, pages_v)
