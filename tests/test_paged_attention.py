"""Paged KV cache + paged batched-decode attention kernel tests.

Three layers of pinning: (1) the Pallas kernel is BITWISE equal to its
jnp oracle (identical f32 op order, including the G-padding applied
before the backend branch); (2) both match an independent full-softmax
dense reference to fp32 tolerance; (3) the paged write path stores the
same bits the dense cache would (``dense_view`` round-trips), and the
dense decode path itself agrees with prefill at every position —
including the ring-buffered sliding-window cache wrapping past capacity.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.kernels import ops
from repro.models import attention as attn_mod
from repro.models import paging

KEY = jax.random.key(0)


def _rand_paged(seed, slots, Hkv, maxp, page, D, lengths):
    """Random pools + a shuffled page-table assignment (pages are NOT
    contiguous per slot — the whole point of the indirection)."""
    rng = np.random.RandomState(seed)
    P = slots * maxp
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    pages_k = jax.random.normal(k1, (Hkv, P, page, D), jnp.float32)
    pages_v = jax.random.normal(k2, (Hkv, P, page, D), jnp.float32)
    table = jnp.asarray(rng.permutation(P).reshape(slots, maxp), jnp.int32)
    q = jax.random.normal(k3, (slots, 1, D), jnp.float32)  # placeholder
    return pages_k, pages_v, table, jnp.asarray(lengths, jnp.int32)


def _dense_softmax_ref(q, pages_k, pages_v, table, lengths):
    """Independent reference: gather to dense, one full softmax per slot
    (no online accumulation — different op order from both backends)."""
    slots, Hq, D = q.shape
    Hkv = pages_k.shape[0]
    G = Hq // Hkv
    kg = np.moveaxis(np.asarray(pages_k)[:, np.asarray(table)], 0, 1)
    vg = np.moveaxis(np.asarray(pages_v)[:, np.asarray(table)], 0, 1)
    maxp, page = kg.shape[2], kg.shape[3]
    T = maxp * page
    kd = kg.reshape(slots, Hkv, T, D).astype(np.float64)
    vd = vg.reshape(slots, Hkv, T, D).astype(np.float64)
    qf = np.asarray(q).reshape(slots, Hkv, G, D).astype(np.float64)
    s = np.einsum("bhgd,bhtd->bhgt", qf, kd) / np.sqrt(D)
    mask = np.arange(T)[None, None, None, :] < np.asarray(lengths)[:, None, None, None]
    s = np.where(mask, s, -np.inf)
    with np.errstate(invalid="ignore"):
        w = np.exp(s - s.max(-1, keepdims=True))
        w = np.nan_to_num(w / np.maximum(w.sum(-1, keepdims=True), 1e-300))
    out = np.einsum("bhgt,bhtd->bhgd", w, vd)
    out[np.asarray(lengths) == 0] = 0.0  # empty slots attend to nothing
    return out.reshape(slots, Hq, D).astype(np.float32)


@pytest.mark.parametrize("slots,Hq,Hkv,D,lengths", [
    (4, 4, 4, 64, [7, 32, 0, 19]),       # ragged incl. dead slot
    (4, 8, 2, 128, [1, 16, 33, 64]),     # page boundaries + full
    (3, 3, 3, 64, [5, 48, 17]),          # G=1 (pad 1->8 before branch)
    (2, 8, 1, 64, [64, 2]),              # MQA, G=8 (no padding)
    (2, 2, 2, 128, [31, 0]),             # G=1, D=128
])
def test_pallas_bitwise_vs_oracle(slots, Hq, Hkv, D, lengths):
    page, maxp = 16, 4
    pages_k, pages_v, table, lens = _rand_paged(7, slots, Hkv, maxp, page,
                                                D, lengths)
    q = jax.random.normal(jax.random.key(slots * Hq + D),
                          (slots, Hq, D), jnp.float32)
    out = ops.paged_attention(q, pages_k, pages_v, table, lens,
                              backend="pallas")
    exp = ops.paged_attention(q, pages_k, pages_v, table, lens,
                              backend="jnp")
    assert np.array_equal(np.asarray(out), np.asarray(exp)), \
        "pallas kernel diverged bitwise from the jnp oracle"


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
@pytest.mark.parametrize("slots,Hq,Hkv,D,lengths", [
    (4, 4, 2, 64, [7, 32, 0, 19]),
    (2, 8, 2, 128, [48, 15]),
])
def test_paged_matches_dense_softmax(backend, slots, Hq, Hkv, D, lengths):
    page, maxp = 16, 4
    pages_k, pages_v, table, lens = _rand_paged(3, slots, Hkv, maxp, page,
                                                D, lengths)
    q = jax.random.normal(jax.random.key(11), (slots, Hq, D), jnp.float32)
    out = ops.paged_attention(q, pages_k, pages_v, table, lens,
                              backend=backend)
    exp = _dense_softmax_ref(q, pages_k, pages_v, table, lens)
    np.testing.assert_allclose(np.asarray(out), exp, atol=2e-5, rtol=2e-5)


def test_dead_slot_exact_zero():
    pages_k, pages_v, table, lens = _rand_paged(5, 3, 2, 2, 16, 64,
                                                [12, 0, 0])
    q = jax.random.normal(jax.random.key(2), (3, 4, 64), jnp.float32)
    for backend in ("pallas", "jnp"):
        out = np.asarray(ops.paged_attention(q, pages_k, pages_v, table,
                                             lens, backend=backend))
        assert (out[1:] == 0.0).all(), backend


def test_non_tile_head_dim_rejected_by_pallas():
    pages_k, pages_v, table, lens = _rand_paged(1, 2, 2, 2, 16, 96, [4, 4])
    q = jnp.zeros((2, 4, 96), jnp.float32)
    with pytest.raises(NotImplementedError):
        ops.paged_attention(q, pages_k, pages_v, table, lens,
                            backend="pallas")
    ops.paged_attention(q, pages_k, pages_v, table, lens, backend="jnp")


# ---------------------------------------------------------------------------
# cache write paths
# ---------------------------------------------------------------------------

def _cfg(**over):
    cfg = reduced_config(get_config("granite-8b"))
    return dataclasses.replace(cfg, **over) if over else cfg


def test_paged_write_roundtrip_and_dead_slot_drop():
    cfg = _cfg()
    slots, page, steps = 3, 8, 5
    cache = paging.init_paged_cache(cfg, slots, 4 * page, page)
    # slot i owns pages [i*4 .. i*4+3]; slot 2 is dead
    table = np.arange(slots * 4, dtype=np.int32).reshape(slots, 4)
    live = np.array([True, True, False])
    cache = cache._replace(page_table=jnp.asarray(table),
                           live=jnp.asarray(live))
    hd = cfg.resolved_head_dim
    written = []
    for t in range(steps):
        k = jax.random.normal(jax.random.key(2 * t),
                              (slots, 1, cfg.num_kv_heads, hd), jnp.float32)
        v = jax.random.normal(jax.random.key(2 * t + 1),
                              (slots, 1, cfg.num_kv_heads, hd), jnp.float32)
        written.append((k, v))
        cache = paging.paged_write(cache, k, v)
    assert np.asarray(cache.lengths).tolist() == [steps, steps, 0]
    kd, vd, valid = paging.dense_view(cache)
    for t, (k, v) in enumerate(written):
        for b in range(2):  # live slots: bitwise round-trip
            assert np.array_equal(np.asarray(kd[b, t]), np.asarray(k[b, 0]))
            assert np.array_equal(np.asarray(vd[b, t]), np.asarray(v[b, 0]))
    # dead slot: every write dropped, its pages still zero
    assert (np.asarray(kd[2]) == 0.0).all()
    assert np.asarray(valid).tolist() == [
        [i < steps for i in range(valid.shape[1])]] * 2 + \
        [[False] * valid.shape[1]]


def test_write_prompt_roundtrip():
    cfg = _cfg()
    page, S = 8, 13  # ragged: straddles a page boundary
    cache = paging.init_paged_cache(cfg, 2, 4 * page, page)
    hd = cfg.resolved_head_dim
    k = jax.random.normal(jax.random.key(0), (1, S, cfg.num_kv_heads, hd))
    v = jax.random.normal(jax.random.key(1), (1, S, cfg.num_kv_heads, hd))
    ids = jnp.asarray([5, 2, 0, 0], jnp.int32)  # non-contiguous pages
    cache = paging.write_prompt(cache, ids, k, v)
    cache = cache._replace(
        page_table=jnp.asarray([[5, 2, 0, 0], [0, 0, 0, 0]], jnp.int32),
        lengths=jnp.asarray([S, 0], jnp.int32),
        live=jnp.asarray([True, False]))
    kd, vd, _ = paging.dense_view(cache)
    assert np.array_equal(np.asarray(kd[0, :S]), np.asarray(k[0]))
    assert np.array_equal(np.asarray(vd[0, :S]), np.asarray(v[0]))


def test_page_allocator_exhaustion_and_double_free():
    a = paging.PageAllocator(4)
    p1 = a.alloc(3)
    assert a.free_pages == 1
    with pytest.raises(MemoryError):
        a.alloc(2)
    a.free(p1[:2])
    assert a.free_pages == 3
    with pytest.raises(ValueError):
        a.free(p1[:1])  # double free
    p2 = a.alloc(3)
    assert sorted(p2 + [p1[2]]) == sorted(set(p2 + [p1[2]]))


def test_init_paged_cache_rejects_sliding_window_and_tiny_pages():
    with pytest.raises(ValueError):
        paging.init_paged_cache(_cfg(sliding_window=32), 2, 64, 16)
    with pytest.raises(ValueError):
        paging.init_paged_cache(_cfg(), 2, 64, 4)


# ---------------------------------------------------------------------------
# dense decode path edge cases (models/attention.py) + paged-vs-dense
# ---------------------------------------------------------------------------

def _roll_decode(params, cfg, x, prefill_len, max_len):
    """Prefill a prefix, then decode the rest token by token."""
    B, S, _ = x.shape
    pos = jnp.arange(prefill_len)[None, :]
    ys = []
    y0, cache = attn_mod.attend_prefill(params, cfg, x[:, :prefill_len],
                                        pos, max_len)
    ys.append(y0)
    for t in range(prefill_len, S):
        yt, cache = attn_mod.attend_decode(params, cfg, x[:, t:t + 1], cache)
        ys.append(yt)
    return jnp.concatenate(ys, axis=1), cache


def _granite_cfg():
    """granite-4.0-h-micro's attention, reduced: NoPE, scores x 1/64."""
    return reduced_config(get_config("granite-4.0-h-micro"))


def test_decode_matches_prefill_every_position():
    _decode_matches_prefill(_cfg())


def test_decode_matches_prefill_nope_scaled():
    _decode_matches_prefill(_granite_cfg())


def _decode_matches_prefill(cfg):
    S = 24
    params = attn_mod.init_attention(jax.random.key(1), cfg)
    x = jax.random.normal(jax.random.key(2), (2, S, cfg.d_model))
    full = attn_mod.attend_train(params, cfg, x, jnp.arange(S)[None, :])
    rolled, _ = _roll_decode(params, cfg, x, 1, S)
    np.testing.assert_allclose(np.asarray(rolled), np.asarray(full),
                               atol=2e-5, rtol=2e-5)


def test_sliding_window_ring_wraparound_past_capacity():
    """Decode far past the ring capacity: the cache keeps exactly the
    last `window` tokens and outputs match full windowed attention."""
    W = 8
    cfg = _cfg(sliding_window=W)
    S = 3 * W + 3  # wraps the ring ~3 times
    params = attn_mod.init_attention(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (1, S, cfg.d_model))
    full = attn_mod.attend_train(params, cfg, x, jnp.arange(S)[None, :])
    rolled, cache = _roll_decode(params, cfg, x, 1, S)
    np.testing.assert_allclose(np.asarray(rolled), np.asarray(full),
                               atol=2e-5, rtol=2e-5)
    assert cache.k.shape[1] == W  # capacity clamped to the window
    assert int(cache.length) == S


def test_prefill_longer_than_capacity_then_decode():
    """attend_prefill's S >= cap ring layout: prefill 2.5 windows, keep
    decoding, stay consistent with full windowed attention."""
    W = 8
    cfg = _cfg(sliding_window=W)
    S0, S = 20, 28
    params = attn_mod.init_attention(jax.random.key(5), cfg)
    x = jax.random.normal(jax.random.key(6), (1, S, cfg.d_model))
    full = attn_mod.attend_train(params, cfg, x, jnp.arange(S)[None, :])
    rolled, _ = _roll_decode(params, cfg, x, S0, S)
    np.testing.assert_allclose(np.asarray(rolled[:, S0 - 1:]),
                               np.asarray(full[:, S0 - 1:]),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_matches_dense_decode():
    """Same prompt, same decode steps: the paged cache stores the exact
    bits the dense cache does, and the paged attend stays within fp32
    tolerance of the dense attend (different softmax op order)."""
    _paged_matches_dense(_cfg())


def test_paged_decode_matches_dense_decode_nope_scaled():
    _paged_matches_dense(_granite_cfg())


def _paged_matches_dense(cfg):
    slots, S, steps, page = 2, 11, 4, 8
    max_len = 32
    params = attn_mod.init_attention(jax.random.key(7), cfg)
    x = jax.random.normal(jax.random.key(8), (slots, S + steps, cfg.d_model))
    pos = jnp.arange(S)[None, :]
    _, dense = attn_mod.attend_prefill(params, cfg, x[:, :S], pos, max_len)

    pcache = paging.init_paged_cache(cfg, slots, max_len, page)
    maxp = pcache.max_pages
    q, k, v = attn_mod._project_qkv(params, cfg, x[:, :S],
                                    jnp.broadcast_to(pos, (slots, S)))
    for b in range(slots):
        ids = jnp.asarray([b * maxp + j for j in range(maxp)], jnp.int32)
        pcache = paging.write_prompt(pcache, ids, k[b:b + 1], v[b:b + 1])
    table = np.arange(slots * maxp, dtype=np.int32).reshape(slots, maxp)
    pcache = pcache._replace(page_table=jnp.asarray(table),
                             lengths=jnp.full((slots,), S, jnp.int32),
                             live=jnp.ones((slots,), bool))
    kd, vd, _ = paging.dense_view(pcache)
    assert np.array_equal(np.asarray(kd[:, :S]), np.asarray(dense.k[:, :S]))
    assert np.array_equal(np.asarray(vd[:, :S]), np.asarray(dense.v[:, :S]))

    for t in range(steps):
        xt = x[:, S + t:S + t + 1]
        yd, dense = attn_mod.attend_decode(params, cfg, xt, dense)
        yp, pcache = paging.attend_decode_paged(params, cfg, xt, pcache)
        np.testing.assert_allclose(np.asarray(yp), np.asarray(yd),
                                   atol=2e-5, rtol=2e-5, err_msg=f"step {t}")
        kd, vd, _ = paging.dense_view(pcache)
        assert np.array_equal(np.asarray(kd[:, :S + t + 1]),
                              np.asarray(dense.k[:, :S + t + 1]))
