"""Fused cut-layer codec kernels: quantize+pack and dequantize+aggregate.

Two memory-bound Pallas kernels around the SFL-GA wire format:

* ``quantize_pack`` — per-client, per-tile symmetric int quantization of
  the smashed tensor (N, T, D) with stochastic rounding, emitting int8
  words (two int4 values packed per word for ``bits=4``) plus one fp32
  scale per (client, tile). One read of g, one write of q — the client-side
  encoder before the uplink.
* ``dequant_agg_reduce`` — the server-side decoder fused with the paper's
  eq. 5 reduction: out[t, d] = Σ_n ρ[n] · scale[n, tile] · q[n, t, d].
  Extends ``kernels/grad_agg.py`` so the server never materializes the
  dequantized per-client tensors: N payloads are unpacked, rescaled and
  ρ-reduced in a single VMEM pass.

Stochastic rounding uses a counter-based hash over *global* (n, t, d)
coordinates and a seed word, so the output is bit-identical between the
tiled kernel and the pure-jnp oracle (``ref.quantize_ref``), independent
of the BlockSpec tiling, and reproducible across backends. (The TPU-only
``pltpu.prng_*`` path is deliberately avoided: it has no interpret-mode
lowering, and the driver's CPU CI runs these kernels interpreted.)

Tiles: (N, bt, bd) input blocks; the client axis N is small (≤ tens) and
rides along fully inside VMEM, matching ``grad_agg.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

# xxhash/murmur-style odd multipliers (uint32 arithmetic wraps mod 2^32)
_K_N = 0x9E3779B1
_K_T = 0x85EBCA77
_K_D = 0xC2B2AE3D
_K_S = 0x27D4EB2F
_M1 = 0x2C1B3C6D
_M2 = 0x297A2D39


def hash_uniform(n, t, d, seed):
    """Counter-based uniform(0,1) from global coords — shared by the Pallas
    kernels and the jnp oracles so both round identically. All inputs are
    uint32 arrays/scalars broadcastable to a common shape."""
    u32 = jnp.uint32
    h = (n * u32(_K_N)) ^ (t * u32(_K_T)) ^ (d * u32(_K_D)) \
        ^ (jnp.asarray(seed, jnp.uint32) * u32(_K_S))
    h = h ^ (h >> 15)
    h = h * u32(_M1)
    h = h ^ (h >> 13)
    h = h * u32(_M2)
    h = h ^ (h >> 16)
    # the top 24 bits fit int32 exactly; Mosaic has no uint32 -> f32 cast
    return (h >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))


def qmax_for(bits: int) -> int:
    assert bits in (4, 8), bits
    return (1 << (bits - 1)) - 1  # 7 / 127 — symmetric, no -2^(b-1) code


def _quantize_kernel(g_ref, seed_ref, q_ref, s_ref, *, qmax, pack,
                     stochastic, block_t, block_d):
    g = g_ref[...].astype(jnp.float32)  # (N, bt, bd)
    # one axis at a time: the TPU compiler aborts on a two-axis reduction
    absmax = jnp.max(jnp.max(jnp.abs(g), axis=2, keepdims=True), axis=1,
                     keepdims=True)  # (N, 1, 1)
    # multiply by the 1/qmax constant rather than divide: XLA strength-
    # reduces constant divides to an approximate reciprocal, which would
    # break bit-equality between the jitted kernel and the eager oracle
    scale = jnp.where(absmax > 0.0, absmax * (1.0 / qmax), 1.0)
    if stochastic:
        n = jax.lax.broadcasted_iota(jnp.uint32, g.shape, 0)
        t = jax.lax.broadcasted_iota(jnp.uint32, g.shape, 1) \
            + (pl.program_id(0) * block_t).astype(jnp.uint32)
        d = jax.lax.broadcasted_iota(jnp.uint32, g.shape, 2) \
            + (pl.program_id(1) * block_d).astype(jnp.uint32)
        u = hash_uniform(n, t, d, seed_ref[0])
    else:
        u = 0.5  # floor(x/s + 0.5) == round-to-nearest
    q = jnp.clip(jnp.floor(g / scale + u), -qmax, qmax).astype(jnp.int32)
    if pack:
        q = pack_int4(q)
    q_ref[...] = q.astype(jnp.int8)
    # one SMEM scalar per (client, tile), in the flat (N, T/bt, D/bd) order
    i, j = pl.program_id(0), pl.program_id(1)
    nt, nd = pl.num_programs(0), pl.num_programs(1)
    for c in range(g.shape[0]):
        s_ref[(c * nt + i) * nd + j] = scale[c, 0, 0]


@functools.partial(jax.jit, static_argnames=(
    "bits", "block_t", "block_d", "stochastic", "interpret"))
def quantize_pack(g, seed=0, bits: int = 8, block_t: int = 256,
                  block_d: int = 256, stochastic: bool = True,
                  interpret: Optional[bool] = None):
    """g: (N, T, D) per-client smashed data/grads. Returns
    (q: (N, T, D·bits/8) int8, scales: (N, T/bt, D/bd) f32)."""
    N, T, D = g.shape
    block_t = min(block_t, T)
    block_d = min(block_d, D)
    assert T % block_t == 0 and D % block_d == 0, (T, D, block_t, block_d)
    pack = bits == 4
    assert not pack or block_d % 2 == 0, block_d
    bdq = block_d // 2 if pack else block_d
    seed_arr = jnp.asarray(seed, jnp.uint32).reshape(1)
    kernel = functools.partial(
        _quantize_kernel, qmax=qmax_for(bits), pack=pack,
        stochastic=stochastic, block_t=block_t, block_d=block_d)
    nt, nd = T // block_t, D // block_d
    q, scales = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((N, T, D // 2 if pack else D), jnp.int8),
            jax.ShapeDtypeStruct((N * nt * nd,), jnp.float32),
        ),
        grid=(nt, nd),
        in_specs=[
            pl.BlockSpec((N, block_t, block_d), lambda i, j: (0, i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((N, block_t, bdq), lambda i, j: (0, i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        # sequential: every grid step writes its scalars into the one
        # SMEM scale array, which a core split would not merge
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(g, seed_arr)
    return q, scales.reshape(N, nt, nd)


def pack_int4(q):
    """(…, bd) int32 in [-8, 7] -> (…, bd/2) int32 words of one tile row:
    element j in the low nibble, element j + bd/2 in the high one. Two
    halves rather than adjacent pairs, because the TPU compiler lowers a
    lane slice but not the (bd/2, 2) reshape that pairing needs."""
    h = q.shape[-1] // 2
    return ((q[..., h:] & 15) << 4) | (q[..., :h] & 15)


def unpack_int4(w):
    """Inverse of :func:`pack_int4`: (…, bd/2) words -> (…, bd) int32."""
    lo = w & 15
    hi = (w >> 4) & 15
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return jnp.concatenate([lo, hi], axis=-1)


def _dequant_agg_kernel(q_ref, s_ref, rho_ref, o_ref, *, pack):
    # client by client: ρ_n is an SMEM scalar, and Mosaic has no matmul
    # that contracts the untiled client axis
    acc = None
    for n in range(q_ref.shape[0]):
        q = q_ref[n].astype(jnp.int32)  # (bt, bdq)
        if pack:
            q = unpack_int4(q)
        scale = s_ref[(n * pl.num_programs(0) + pl.program_id(0))
                      * pl.num_programs(1) + pl.program_id(1)]
        g = (q.astype(jnp.float32) * scale) * rho_ref[n]
        acc = g if acc is None else acc + g
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "bits", "block_t", "block_d", "interpret"))
def dequant_agg_reduce(q, scales, rho, bits: int = 8, block_t: int = 256,
                       block_d: int = 256, interpret: Optional[bool] = None):
    """Fused decode + eq. 5: Σ_n ρ[n]·scale[n,tile]·q[n]. q: (N, T, Dq)
    int8 payloads from ``quantize_pack``; scales: (N, T/bt, D/bd);
    rho: (N,). The (block_t, block_d) tiling must match the encoder's —
    it defines the scale granularity on the wire. Returns (T, D) f32."""
    N, T, Dq = q.shape
    pack = bits == 4
    D = Dq * 2 if pack else Dq
    block_t = min(block_t, T)
    block_d = min(block_d, D)
    assert scales.shape == (N, T // block_t, D // block_d), (
        scales.shape, (N, T // block_t, D // block_d))
    bdq = block_d // 2 if pack else block_d
    return pl.pallas_call(
        functools.partial(_dequant_agg_kernel, pack=pack),
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        grid=(T // block_t, D // block_d),
        in_specs=[
            pl.BlockSpec((N, block_t, bdq), lambda i, j: (0, i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_t, block_d), lambda i, j: (i, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=resolve_interpret(interpret),
    )(q, scales.astype(jnp.float32).reshape(-1), rho.astype(jnp.float32))
