"""Mamba-2 SSD intra-chunk kernel (TPU Pallas).

Computes, per (batch, head, chunk) grid cell, entirely in VMEM:

  L      = exp(segsum(dA))                      (chunk x chunk decay)
  y_diag = (C B^T ⊙ L) @ (x·dt)                 intra-chunk (dual form)
  state  = (B ⊙ decay_to_end)^T @ (x·dt)        chunk-final state

The O(chunks) inter-chunk recurrence (tiny: one (P,N) GEMM per chunk) and
the off-diagonal contribution stay in jnp — see repro.kernels.ops.ssd.

TPU adaptation: the CUDA version's warp-level scan becomes a chunk x chunk
lower-triangular matmul feeding the MXU; chunk length (128) and head_dim
(64) are lane-aligned; the decay matrix is built in VMEM from the chunk's
cumulative log-decay, which XLA computes before the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _ssd_chunk_kernel(xdt_ref, csr_ref, csc_ref, b_ref, c_ref, y_ref,
                      state_ref):
    # blocks: xdt (1,1,1,Q,P), cumulative log-decay as a row (1,1,1,1,Q)
    # and as a column (1,1,1,Q,1), b/c (1,1,1,Q,N)
    xdt = xdt_ref[0, 0, 0].astype(jnp.float32)  # (Q, P)
    cs_row = csr_ref[0, 0, 0]  # (1, Q)
    cs_col = csc_ref[0, 0, 0]  # (Q, 1)
    B = b_ref[0, 0, 0].astype(jnp.float32)  # (Q, N)
    C = c_ref[0, 0, 0].astype(jnp.float32)  # (Q, N)
    Q = xdt.shape[0]

    # segsum: seg[i, j] = cs[i] - cs[j]; valid lower triangle (j <= i)
    seg = cs_col - cs_row
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(jj <= ii, jnp.exp(seg), 0.0)  # (Q, Q)

    # intra-chunk: y = (C B^T ⊙ L) @ xdt
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    y = jax.lax.dot_general(cb * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, P)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # chunk-final state: state[p, n] = Σ_q exp(cs[-1]-cs[q]) B[q,n] xdt[q,p]
    decay = jnp.exp(cs_row[:, Q - 1:] - cs_col)  # (Q, 1)
    bw = B * decay  # (Q, N)
    state = jax.lax.dot_general(xdt, bw, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (P, N)
    state_ref[0, 0, 0] = state


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk(xdt, dA, B, C, interpret: Optional[bool] = None):
    """xdt: (b, h, c, Q, P) x·dt; dA: (b, h, c, Q) log-decay;
    B, C: (b, h, c, Q, N) head-expanded. Returns (y_diag, chunk_states)."""
    b, h, c, Q, P = xdt.shape
    N = B.shape[-1]
    grid = (b, h, c)
    # the chunk's cumulative log-decay, in both layouts the kernel needs:
    # Mosaic lowers neither a cumsum nor a (1, Q) -> (Q, 1) transpose
    cs = jnp.cumsum(dA.astype(jnp.float32), axis=-1)
    y, states = pl.pallas_call(
        _ssd_chunk_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b, h, c, Q, P), xdt.dtype),
            jax.ShapeDtypeStruct((b, h, c, P, N), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N), lambda i, j, k: (i, j, k, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, 1, Q, P), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, P, N), lambda i, j, k: (i, j, k, 0, 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=resolve_interpret(interpret),
    )(xdt, cs[..., None, :], cs[..., None], B, C)
    return y, states
