"""ρ-weighted smashed-gradient aggregation kernel (paper eq. 5).

out[t, d] = Σ_n ρ[n] · g[n, t, d] — the server-side reduction performed on
every round before the gradient broadcast. Memory-bound by construction;
the kernel exists so the paper's core op is a single fused VMEM pass
(one read of g, one write of out) instead of a materialized
weighted-multiply + reduce pair.

Tiles: (N, bt, bd) input blocks reduced to (bt, bd) output blocks; the
client axis N is small (≤ tens) and rides along fully inside VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _grad_agg_kernel(g_ref, rho_ref, o_ref):
    # client by client: ρ_n is an SMEM scalar, and Mosaic has no matmul
    # that contracts the untiled client axis
    acc = g_ref[0].astype(jnp.float32) * rho_ref[0]
    for n in range(1, g_ref.shape[0]):
        acc = acc + g_ref[n].astype(jnp.float32) * rho_ref[n]
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_d", "interpret"))
def grad_agg_reduce(g, rho, block_t: int = 256, block_d: int = 256,
                    interpret: Optional[bool] = None):
    """g: (N, T, D) per-client smashed grads; rho: (N,). Returns (T, D)."""
    N, T, D = g.shape
    block_t = min(block_t, T)
    block_d = min(block_d, D)
    assert T % block_t == 0 and D % block_d == 0, (T, D, block_t, block_d)
    return pl.pallas_call(
        _grad_agg_kernel,
        out_shape=jax.ShapeDtypeStruct((T, D), g.dtype),
        grid=(T // block_t, D // block_d),
        in_specs=[
            pl.BlockSpec((N, block_t, block_d), lambda i, j: (0, i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_t, block_d), lambda i, j: (i, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=resolve_interpret(interpret),
    )(g, rho.astype(jnp.float32))
