"""Compile the Pallas kernels for a described TPU v5e, no chip attached.

Interpret mode runs on the CPU and accepts kernels the TPU compiler
refuses (unaligned tiles, too much VMEM). These tests compile each
kernel of the main path with ``interpret=False`` at the widths it runs at
— granite-8b attention and split boundary, mamba2-130m SSD — and check
that the program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.grad_agg import grad_agg_reduce
from repro.kernels.paged_attention import paged_attention_decode
from repro.kernels.quantize import dequant_agg_reduce, quantize_pack
from repro.kernels.ssd_scan import ssd_intra_chunk


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, shapes, sharding, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    lowered = fn.lower(*args, interpret=False, **static)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


# granite-8b: 32 query heads over 8 KV heads of width 128, d_model 4096;
# the split boundary of a step is K=4 clients x b=1 x S=512 tokens
HQ, HKV, D, DM, K, S = 32, 8, 128, 4096, 4, 512


def test_flash_attention(one_chip, no_compile_cache):
    f32 = jnp.float32
    _compile(flash_attention_bhsd,
             [((1, HQ, S, D), f32), ((1, HKV, S, D), f32),
              ((1, HKV, S, D), f32)], one_chip)


def test_paged_attention_decode(one_chip, no_compile_cache):
    # 4 slots of up to 160 tokens in pages of 16; the GQA group (4 query
    # heads per KV head) is padded to the 8-row sublane tile by ops
    slots, page, max_pages = 4, 16, 10
    pool = (HKV, slots * max_pages, page, D)
    _compile(paged_attention_decode,
             [((slots, HKV, 8, D), jnp.float32), (pool, jnp.float32),
              (pool, jnp.float32), ((slots, max_pages), jnp.int32),
              ((slots,), jnp.int32)], one_chip)


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_pack(one_chip, no_compile_cache, bits):
    _compile(quantize_pack, [((K, S, DM), jnp.float32), ((), jnp.uint32)],
             one_chip, bits=bits)


@pytest.mark.parametrize("bits", [8, 4])
def test_dequant_agg_reduce(one_chip, no_compile_cache, bits):
    dq = DM * bits // 8
    _compile(dequant_agg_reduce,
             [((K, S, dq), jnp.int8), ((K, S // 256, DM // 256), jnp.float32),
              ((K,), jnp.float32)], one_chip, bits=bits)


def test_grad_agg_reduce(one_chip, no_compile_cache):
    _compile(grad_agg_reduce,
             [((K, 2048, DM), jnp.float32), ((K,), jnp.float32)], one_chip)


def test_ssd_intra_chunk(one_chip, no_compile_cache):
    # mamba2-130m: d_inner 1536 = 24 heads of 64, state 128; 4 chunks of
    # 128 tokens
    b, h, c, q, p, n = 1, 24, 4, 128, 64, 128
    f32 = jnp.float32
    _compile(ssd_intra_chunk,
             [((b, h, c, q, p), f32), ((b, h, c, q), f32),
              ((b, h, c, q, n), f32), ((b, h, c, q, n), f32)], one_chip)
