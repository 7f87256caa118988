"""Cut-layer transport codecs: the lossy wire format of split learning.

Every tensor crossing the client/server boundary — uplink smashed data
X(v), the broadcast aggregated gradient of eq. 5, per-client gradient
unicast — goes through a ``Codec``: ``encode`` produces a ``Payload``
(quantized values + side-channel scales/indices), ``decode`` reconstructs
the tensor, ``payload_bits`` prices it for the system model. All codecs
are functional and jit/vmap-safe; stochastic rounding derives from an
explicit uint32 ``seed`` (the shared counter-based hash of
``kernels.quantize``), never from ambient state.

Implementations:

* ``PassthroughCodec`` — fp32 identity; ``roundtrip`` returns its input
  object unchanged, so wiring it through a training graph is a no-op and
  reproduces uncompressed metrics bit-for-bit.
* ``CastCodec`` — bf16 / fp8(e4m3) element casts.
* ``IntQuantCodec`` — int8/int4 symmetric quantization with per-tile fp32
  scales and stochastic rounding; tile size matches the Pallas kernels'
  on-wire scale granularity.
* ``TopKCodec`` — magnitude top-k sparsification (fp32 values + int32
  indices) with optional per-client error-feedback state: the residual
  every round is carried into the next ``encode_ef`` call, the standard
  EF-SGD construction (Karimireddy et al., 2019).

Bit accounting lives in ``repro.sysmodel.payload`` (one ``PayloadSpec``
per codec name) so numpy-only system-model code prices payloads without
importing jax.
"""
from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sysmodel.payload import PayloadSpec, spec_for


class Payload:
    """Encoded tensor: array children + static (shape, dtype, codec) aux,
    registered as a pytree so payloads flow through jit/vmap/scan."""

    def __init__(self, data, scale=None, meta=None, *, shape, dtype, codec):
        self.data = data
        self.scale = scale
        self.meta = meta
        self.shape = tuple(shape)
        self.dtype = dtype
        self.codec = codec

    def tree_flatten(self):
        return (self.data, self.scale, self.meta), (self.shape, self.dtype,
                                                    self.codec)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scale, meta = children
        shape, dtype, codec = aux
        return cls(data, scale, meta, shape=shape, dtype=dtype, codec=codec)

    def __repr__(self):
        return (f"Payload(codec={self.codec!r}, shape={self.shape}, "
                f"data={getattr(self.data, 'shape', None)})")


jax.tree_util.register_pytree_node(
    Payload, Payload.tree_flatten, Payload.tree_unflatten)


class Codec:
    """Base codec. Stateless by default; stateful codecs (error feedback)
    override ``init_state``/``encode_ef``."""

    name: str = "base"
    is_identity: bool = False

    @property
    def spec(self) -> PayloadSpec:
        return spec_for(self.name)

    # -- core protocol -------------------------------------------------
    def encode(self, x: jnp.ndarray, seed=0) -> Payload:
        raise NotImplementedError

    def decode(self, p: Payload) -> jnp.ndarray:
        raise NotImplementedError

    def payload_bits(self, shape: Tuple[int, ...]) -> int:
        return self.spec.payload_bits(int(math.prod(shape)))

    # -- conveniences --------------------------------------------------
    def roundtrip(self, x: jnp.ndarray, seed=0) -> jnp.ndarray:
        """decode(encode(x)) — the lossy channel as one differentiable-
        graph-friendly op (used inside simulator/vjp wiring)."""
        return self.decode(self.encode(x, seed))

    # -- error feedback (no-op for memoryless codecs) ------------------
    def init_state(self, shape: Tuple[int, ...]):
        return None

    def encode_ef(self, x: jnp.ndarray, state, seed=0):
        """Encode with error feedback: returns (payload, new_state)."""
        return self.encode(x, seed), state


class PassthroughCodec(Codec):
    name = "fp32"
    is_identity = True

    def encode(self, x, seed=0):
        return Payload(x, shape=x.shape, dtype=x.dtype, codec=self.name)

    def decode(self, p):
        return p.data

    def roundtrip(self, x, seed=0):
        return x


class CastCodec(Codec):
    def __init__(self, name: str, wire_dtype):
        self.name = name
        self.wire_dtype = wire_dtype

    def encode(self, x, seed=0):
        return Payload(x.astype(self.wire_dtype), shape=x.shape,
                       dtype=x.dtype, codec=self.name)

    def decode(self, p):
        return p.data.astype(p.dtype)


class IntQuantCodec(Codec):
    """Symmetric absmax quantization over flat tiles of ``tile`` elements,
    one fp32 scale each; int4 packs each tile's two halves into int8 words
    (``kernels.quantize.pack_int4``, the kernels' wire format). The flat
    layout makes the codec shape-agnostic (conv maps, sequences, params);
    the (N, T, D) kernel entry points in ``kernels.ops`` are the layout-
    specialized fast path for the server's aggregation inner loop."""

    def __init__(self, bits: int, tile: int = 256, stochastic: bool = True):
        assert bits in (4, 8), bits
        self.name = f"int{bits}"
        self.bits = bits
        self.tile = tile
        self.stochastic = stochastic
        assert tile == spec_for(self.name).tile, (
            "tile must match the PayloadSpec wire format")

    @property
    def qmax(self) -> int:
        return (1 << (self.bits - 1)) - 1

    def _flatten(self, x):
        numel = int(math.prod(x.shape))
        pad = (-numel) % self.tile
        flat = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, pad))
        return flat.reshape(-1, self.tile), numel

    def encode(self, x, seed=0):
        from repro.kernels.quantize import hash_uniform, pack_int4

        tiles, numel = self._flatten(x)
        absmax = jnp.max(jnp.abs(tiles), axis=1, keepdims=True)
        scale = jnp.where(absmax > 0.0, absmax * (1.0 / self.qmax), 1.0)
        if self.stochastic:
            idx = jax.lax.broadcasted_iota(jnp.uint32, tiles.shape, 0) \
                * jnp.uint32(self.tile) \
                + jax.lax.broadcasted_iota(jnp.uint32, tiles.shape, 1)
            u = hash_uniform(jnp.uint32(0), jnp.uint32(0), idx, seed)
        else:
            u = 0.5
        q = jnp.clip(jnp.floor(tiles / scale + u),
                     -self.qmax, self.qmax).astype(jnp.int32)
        if self.bits == 4:
            q = pack_int4(q)
        return Payload(q.astype(jnp.int8), scale[:, 0], shape=x.shape,
                       dtype=x.dtype, codec=self.name)

    def decode(self, p):
        q = p.data.astype(jnp.int32)
        if self.bits == 4:
            from repro.kernels.quantize import unpack_int4

            q = unpack_int4(q)
        x = q.astype(jnp.float32) * p.scale[:, None]
        numel = int(math.prod(p.shape))
        return x.reshape(-1)[:numel].reshape(p.shape).astype(p.dtype)


class TopKCodec(Codec):
    """Magnitude top-k over the flattened tensor; ``density`` is the kept
    fraction. ``encode_ef`` implements error feedback: the quantization
    residual accumulates client-side and is re-offered next round."""

    def __init__(self, density: float):
        # whole percents only: the name IS the wire format ('topkP'), and
        # payload accounting (sysmodel.payload) prices by that name — a
        # non-representable density would silently misprice the channel
        pct = round(density * 100)
        if not (1 <= pct <= 99 and abs(density * 100 - pct) < 1e-9):
            raise ValueError(
                f"TopKCodec density must be a whole percent in "
                f"[0.01, 0.99], got {density}")
        self.name = f"topk{pct}"
        self.density = pct / 100.0

    def _k(self, numel: int) -> int:
        return max(1, math.ceil(numel * self.density))

    def encode(self, x, seed=0):
        flat = x.reshape(-1).astype(jnp.float32)
        k = self._k(flat.shape[0])
        _, idx = jax.lax.top_k(jnp.abs(flat), k)
        return Payload(flat[idx], meta=idx.astype(jnp.int32), shape=x.shape,
                       dtype=x.dtype, codec=self.name)

    def decode(self, p):
        numel = int(math.prod(p.shape))
        flat = jnp.zeros((numel,), jnp.float32).at[p.meta].set(p.data)
        return flat.reshape(p.shape).astype(p.dtype)

    def init_state(self, shape):
        return jnp.zeros(shape, jnp.float32)

    def encode_ef(self, x, state, seed=0):
        offered = x.astype(jnp.float32) + state
        payload = self.encode(offered, seed)
        new_state = offered - self.decode(payload).astype(jnp.float32)
        return payload, new_state


_FP8 = getattr(jnp, "float8_e4m3fn", None)


def get_codec(codec) -> Codec:
    """Codec by name ('fp32', 'bf16', 'fp8', 'int8', 'int4', 'topkP') or
    pass an existing Codec through unchanged."""
    if isinstance(codec, Codec):
        return codec
    if codec is None or codec == "fp32":
        return PassthroughCodec()
    if codec == "bf16":
        return CastCodec("bf16", jnp.bfloat16)
    if codec == "fp8":
        if _FP8 is None:  # pragma: no cover - depends on jax build
            raise ValueError("this jax build has no float8_e4m3fn dtype")
        return CastCodec("fp8", _FP8)
    if codec == "int8":
        return IntQuantCodec(8)
    if codec == "int4":
        return IntQuantCodec(4)
    spec = spec_for(codec)  # raises KeyError with the known-name list
    return TopKCodec(spec.density)


def codec_names() -> Tuple[str, ...]:
    return ("fp32", "bf16", "fp8", "int8", "int4")
