"""Paged KV cache: fixed-size pages, per-slot page tables (DESIGN.md §18).

The dense :class:`~repro.models.attention.KVCache` reserves
``slots × max_len`` key/value rows up front and shares ONE scalar write
position across the batch — fine for lock-step batch decode, fatal for a
continuous-batching server where every slot sits at a different position
and most reserved rows would never hold a live token. Here the cache is
a pool of fixed-size pages:

* ``pages_k``/``pages_v`` — ``(Hkv, num_pages, page_size, head_dim)``
  physical pools, head-major so one (head, page) tile is a contiguous
  ``(page_size, head_dim)`` VMEM block for the Pallas kernel.
* ``page_table`` — ``(slots, max_pages)`` int32: logical page ``j`` of a
  slot lives in physical page ``page_table[slot, j]``. Allocation is
  host-driven (:class:`PageAllocator`): pages are claimed as a slot's
  context crosses a page boundary and returned the moment the request
  retires, so cache memory scales with LIVE tokens, not
  ``max_len × slots``.
* ``lengths`` — ``(slots,)`` int32 per-slot token counts (the per-slot
  decode position the dense cache cannot express).
* ``live`` — ``(slots,)`` bool; dead slots neither write nor advance, so
  the jitted decode step runs fixed shapes while retired slots idle.

The attention over this layout is ``kernels.paged_attention`` (one query
token per slot gathered against its page list); ``dense_view`` rebuilds
the dense cache for the jnp oracle and the paged-vs-dense parity tests.

Paged attention is full-causal only: sliding-window models keep the
ring-buffered dense decode path (``attend_decode``), which is already
O(window).
"""
from __future__ import annotations

import math
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models.blocks import linear


class PagedKVCache(NamedTuple):
    pages_k: jnp.ndarray   # (Hkv, P, page_size, D) physical pool
    pages_v: jnp.ndarray   # (Hkv, P, page_size, D)
    page_table: jnp.ndarray  # (slots, max_pages) int32 physical page ids
    lengths: jnp.ndarray   # (slots,) int32 tokens written per slot
    live: jnp.ndarray      # (slots,) bool — dead slots are frozen

    @property
    def page_size(self) -> int:
        return self.pages_k.shape[2]

    @property
    def num_pages(self) -> int:
        return self.pages_k.shape[1]

    @property
    def slots(self) -> int:
        return self.page_table.shape[0]

    @property
    def max_pages(self) -> int:
        return self.page_table.shape[1]


def pages_for(tokens: int, page_size: int) -> int:
    """Pages needed to hold ``tokens`` tokens (allocate-on-write unit)."""
    return max(0, -(-int(tokens) // int(page_size)))


def init_paged_cache(cfg: ModelConfig, slots: int, max_len: int,
                     page_size: int = 16, num_pages: Optional[int] = None,
                     dtype=jnp.float32) -> PagedKVCache:
    """Empty paged cache for one attention layer.

    ``num_pages`` defaults to full occupancy (``slots × ceil(max_len /
    page_size)``); a server oversubscribing memory passes fewer and lets
    admission control block when the pool runs dry.
    """
    if cfg.sliding_window is not None:
        raise ValueError("paged KV cache is full-causal only; sliding-window "
                         "models keep the ring-buffered dense decode path")
    if page_size < 8:
        raise ValueError(f"page_size {page_size} < 8 (TPU f32 sublane tile)")
    hd = cfg.resolved_head_dim
    max_pages = pages_for(max_len, page_size)
    if num_pages is None:
        num_pages = slots * max_pages
    z = jnp.zeros((cfg.num_kv_heads, num_pages, page_size, hd), dtype)
    return PagedKVCache(
        pages_k=z, pages_v=z,
        page_table=jnp.zeros((slots, max_pages), jnp.int32),
        lengths=jnp.zeros((slots,), jnp.int32),
        live=jnp.zeros((slots,), bool),
    )


def paged_write(cache: PagedKVCache, k: jnp.ndarray,
                v: jnp.ndarray) -> PagedKVCache:
    """Write one token per LIVE slot at its own position; dead slots drop.

    k/v: ``(slots, 1, Hkv, D)`` (the ``_project_qkv`` layout). The target
    physical row of slot ``b`` is ``(page_table[b, len_b // page],
    len_b % page)``; dead slots are routed to an out-of-range page id and
    discarded by the scatter's ``mode="drop"`` — no branch, fixed shapes.
    """
    page = cache.page_size
    pos = cache.lengths
    logical = jnp.minimum(pos // page, cache.max_pages - 1)
    phys = jnp.take_along_axis(cache.page_table, logical[:, None], axis=1)[:, 0]
    phys = jnp.where(cache.live, phys, cache.num_pages)  # OOB -> dropped
    off = pos % page
    hkv = cache.pages_k.shape[0]
    hi = jnp.arange(hkv)[:, None]            # (Hkv, 1)
    pi = phys[None, :]                       # (1, slots)
    oi = off[None, :]                        # (1, slots)
    kv = jnp.swapaxes(k[:, 0], 0, 1).astype(cache.pages_k.dtype)  # (Hkv,B,D)
    vv = jnp.swapaxes(v[:, 0], 0, 1).astype(cache.pages_v.dtype)
    return cache._replace(
        pages_k=cache.pages_k.at[hi, pi, oi].set(kv, mode="drop"),
        pages_v=cache.pages_v.at[hi, pi, oi].set(vv, mode="drop"),
        lengths=pos + cache.live.astype(jnp.int32),
    )


def write_prompt(cache: PagedKVCache, slot_page_ids: jnp.ndarray,
                 k: jnp.ndarray, v: jnp.ndarray) -> PagedKVCache:
    """Scatter a prefilled prompt's dense K/V rows into a slot's pages.

    ``slot_page_ids``: ``(max_pages,)`` int32 — the slot's (freshly
    allocated) physical pages; ``k``/``v``: ``(1, S, Hkv, D)`` from
    ``attend_prefill`` with capacity exactly S. Lengths/table/live are
    host-owned admission state and updated via ``_replace`` by the
    engine, not here.
    """
    S = k.shape[1]
    page = cache.page_size
    s = jnp.arange(S)
    pi = slot_page_ids[s // page][None, :]   # (1, S)
    oi = (s % page)[None, :]                 # (1, S)
    hkv = cache.pages_k.shape[0]
    hi = jnp.arange(hkv)[:, None]            # (Hkv, 1)
    kv = jnp.swapaxes(k[0], 0, 1).astype(cache.pages_k.dtype)  # (Hkv, S, D)
    vv = jnp.swapaxes(v[0], 0, 1).astype(cache.pages_v.dtype)
    return cache._replace(
        pages_k=cache.pages_k.at[hi, pi, oi].set(kv, mode="drop"),
        pages_v=cache.pages_v.at[hi, pi, oi].set(vv, mode="drop"),
    )


def dense_view(cache: PagedKVCache) -> Tuple[jnp.ndarray, jnp.ndarray,
                                             jnp.ndarray]:
    """Rebuild ``(k, v, valid)`` dense tensors — ``k``/``v``:
    ``(slots, max_pages*page, Hkv, D)``, ``valid``: bool ``(slots, T)``.
    The oracle/debug inverse of the paged layout (tests pin bit-equality
    of the gathered rows against what was written)."""
    kg = cache.pages_k[:, cache.page_table]  # (Hkv, slots, maxp, page, D)
    vg = cache.pages_v[:, cache.page_table]
    hkv, slots, maxp, page, d = kg.shape
    k = kg.reshape(hkv, slots, maxp * page, d).transpose(1, 2, 0, 3)
    v = vg.reshape(hkv, slots, maxp * page, d).transpose(1, 2, 0, 3)
    valid = jnp.arange(maxp * page)[None, :] < cache.lengths[:, None]
    return k, v, valid


def attend_decode_paged(params, cfg: ModelConfig, x, cache: PagedKVCache,
                        impl: str = "jnp"):
    """One-token GQA decode against the paged cache. x: ``(slots, 1, d)``.

    Each slot's new token sits at its OWN position ``lengths[b]`` (RoPE
    per slot — the dense ``attend_decode`` shares one scalar position
    across the batch and cannot serve a continuous batch). The write
    happens before the attend, so the query sees itself; ``impl="flash"``
    selects the Pallas kernel, anything else the bit-parity jnp oracle.
    """
    if cfg.logit_softcap:
        raise NotImplementedError("paged decode does not support "
                                  "logit_softcap models")
    from repro.kernels import ops as kops

    B = x.shape[0]
    positions = cache.lengths[:, None].astype(jnp.int32)  # (B, 1) per slot
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(positions[None], (3, B, 1))
    q, k, v = attn_mod._project_qkv(params, cfg, x, positions)
    cache = paged_write(cache, k, v)
    out = kops.paged_attention(
        q[:, 0], cache.pages_k, cache.pages_v, cache.page_table,
        cache.lengths, backend="pallas" if impl == "flash" else "jnp")
    return linear(params["wo"], out.reshape(B, 1, -1)), cache


# ---------------------------------------------------------------------------
# Host-side page allocation (admission control is host-driven, like the
# bank's cohort staging: the jitted step never allocates)
# ---------------------------------------------------------------------------

class PageAllocator:
    """Free-list over the physical pool. ``alloc`` claims pages for a
    slot (admission / page-boundary crossing), ``free`` returns them at
    retirement. Raises when the pool is exhausted — the engine treats
    that as \"admission blocked\", never as silent eviction."""

    def __init__(self, num_pages: int):
        self.num_pages = int(num_pages)
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(f"page pool exhausted: want {n}, "
                              f"free {len(self._free)}/{self.num_pages}")
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            if not 0 <= p < self.num_pages:
                raise ValueError(f"freeing invalid page id {p}")
            if p in self._free:
                raise ValueError(f"double free of page {p}")
            self._free.append(p)


# ---------------------------------------------------------------------------
# Group-cache plumbing (mirrors transformer.init_group_caches, with paged
# caches on attention layers; SSM layers keep their O(1) recurrent state)
# ---------------------------------------------------------------------------

def init_paged_group_caches(cfg: ModelConfig, groups, slots: int,
                            max_len: int, page_size: int = 16,
                            num_pages: Optional[int] = None,
                            dtype=jnp.float32):
    """Cache skeleton for ``apply_groups_decode`` with paged attention.

    Every attention layer gets its own physical pool, but all layers
    share ONE logical page table (the engine broadcasts table updates
    with :func:`replace_tables`) — a slot's pages mean the same physical
    ids in every layer's pool."""
    caches = []
    for g in groups:
        per_layer = []
        for s in g.period:
            if s[0] == "attn":
                per_layer.append(init_paged_cache(cfg, slots, max_len,
                                                  page_size, num_pages, dtype))
            else:
                per_layer.append(ssm_mod.init_ssm_cache(cfg, slots, dtype))
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (g.repeat,) + a.shape),
            tuple(per_layer))
        caches.append(stacked)
    return caches


def map_paged(caches, fn):
    """Apply ``fn`` to every (stacked) PagedKVCache in a group-cache list."""
    out = []
    for gc in caches:
        out.append(tuple(fn(c) if isinstance(c, PagedKVCache) else c
                         for c in gc))
    return out


def replace_tables(caches, page_table: np.ndarray, lengths: np.ndarray,
                   live: np.ndarray):
    """Push host-owned admission state (table / lengths / live) into every
    layer's paged cache. The leading axis of each stacked cache is the
    scan layer axis; the admission state is identical across layers."""
    table = jnp.asarray(page_table, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    lv = jnp.asarray(live, bool)

    def upd(c):
        L = c.page_table.shape[0]
        return c._replace(
            page_table=jnp.broadcast_to(table, (L,) + table.shape),
            lengths=jnp.broadcast_to(lens, (L,) + lens.shape),
            live=jnp.broadcast_to(lv, (L,) + lv.shape))

    return map_paged(caches, upd)


def paged_cache_stats(caches, lengths) -> dict:
    """Live-token / page occupancy summary for obs (`serve` events).
    ``lengths`` is the host's copy of the caches' per-slot lengths; of the
    caches only shapes are read, so nothing waits on the device."""
    pages = tokens = pools = 0
    lens = np.asarray(lengths)
    for gc in caches:
        for c in gc:
            if not isinstance(c, PagedKVCache):
                continue
            # stacked layout: pages_k (L, Hkv, P, page, D), lengths (L, slots)
            L = c.page_table.shape[0]
            page = int(c.pages_k.shape[3])
            tokens += int(lens.sum()) * L
            pages += sum(pages_for(int(t), page) for t in lens) * L
            pools += int(c.pages_k.shape[2]) * L
    return {"live_tokens": tokens, "pages_in_use": pages,
            "pages_total": pools}
