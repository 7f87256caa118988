"""Client bank: where the O(N) per-client state lives (DESIGN.md §15).

After the cohort engine (DESIGN.md §13) the server model is O(1), but
the client-side stacks — one model (and, LM-side, optimizer moments)
per registered client — are still O(N), and a single device-resident
``(N,)``-stacked pytree is the wall between fig11 and "millions of
users": ~8.3 MB at N=10k, ~830 MB at N=1M. Each round only ever touches
a K-client cohort (K ≪ N), and the :class:`repro.core.cohort.
CohortSampler` is pure in ``(seed, t)``, so next round's K-slice is
knowable in advance. :class:`ClientBank` exploits exactly that, behind
three interchangeable backends:

``device``
    Today's layout: the stacked pytree lives on device, gathers and
    scatters are device-side indexing through this module's compiled
    programs (``gather_rows``, ``scatter_rows``, ``broadcast_row0``):
    one launch for the whole tree, not one per leaf. The default, and
    the bit-parity baseline — every operation is the exact pre-bank
    expression.
``host``
    The bank lives in host (numpy) memory. A round gathers only the
    K-slice onto device and scatters it back; device memory for client
    state is O(K) regardless of N. A single background worker
    double-buffers the pipeline: while round t trains, round t+1's
    slice is staged host→device (``prefetch``) and round t's updates
    drain device→host (``scatter``) — both off the hot path, so
    steady-state rounds hide the copies entirely. The worker serializes
    its tasks in submission order, which is the correctness argument:
    a prefetch enqueued after a scatter observes that scatter's writes,
    and the caller only enqueues a prefetch BEFORE the pending scatter
    when the two cohorts are disjoint (see ``FedSimulator``).
``sharded``
    The bank is one jax.Array per leaf, sharded over the client axes of
    a ``launch.mesh`` mesh (``launch.shardings.bank_sharding``) — the
    multi-host answer, finally reusing the mesh/sharding layer beyond
    the LLM path. Gathers/scatters are cross-shard device indexing
    through the same compiled programs as ``device``; per-device
    client-state memory is O(N / shards).

The bank is structure-agnostic: it owns any pytree whose leaves carry a
leading ``(N,)`` axis when ``stacked`` (the simulator's list of layer
blocks, the LM path's ``params["client"]`` subtree, an optimizer-moment
tree), or a single-copy pytree when not (the collapsed sfl/fl banks,
which are O(1) anyway and always effectively device-resident).

Whole-bank reductions (the evaluation-time ρ-mean, the ``set_cut``
anchored merge, the Γ drift metric) stream the bank through device in
``chunk_rows`` slices; with one chunk (every N ≤ chunk_rows, and always
on the ``device`` backend) the computation is literally the pre-bank
expression, bit for bit. Multi-chunk reductions accumulate partial f32
sums in chunk order — last-ulp divergence from the single-chunk result
is possible at N > chunk_rows and documented in DESIGN.md §15.

Instrumentation (``repro.obs``): the recorder active at construction is
captured for the bank's lifetime. Host spans, which are also profiler
annotations (PERF.md §3): ``bank/gather`` and ``bank/scatter`` around the
round path's calls, ``bank/wait`` while a round blocks on the staged slice
(~0 when prefetch hid the copy), and on the worker thread
``bank/prefetch`` (the host→device stage) and ``bank/drain`` (the
device→host scatter). Counters ``bank_prefetch_hit`` /
``bank_prefetch_miss``, and ``stats()`` for benchmarks: resident bytes,
peak device bytes, hit rates, total gather wait. The fig11 acceptance bar
reads ``device_bytes_peak``.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

BANK_BACKENDS = ("device", "host", "sharded")

# whole-bank reductions stream through device this many rows at a time;
# one chunk (N <= chunk_rows) reproduces the unchunked expression exactly
DEFAULT_CHUNK_ROWS = 65536


def tree_nbytes(tree) -> int:
    """Total payload bytes of a pytree of arrays (np or jax)."""
    return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


def _reshape_w(w, p):
    return jnp.asarray(w).reshape((-1,) + (1,) * (p.ndim - 1))


# One compiled program per cohort gather / scatter (DESIGN.md §13): the
# per-leaf expressions under one jit, with ``idx`` traced so every cohort
# of the same size K reuses the program (one per tree structure and K).
# No donation: callers keep the old bank after a scatter. Every device-
# side mover of client rows goes through these three: the ``device`` and
# ``sharded`` backends below, ``algorithms.gather_cohort``/
# ``scatter_cohort`` and the LM launcher's async merge.
@jax.jit
def gather_rows(tree, idx):
    return jax.tree.map(lambda x: x[idx], tree)


@jax.jit
def scatter_rows(bank, cohort, idx):
    return jax.tree.map(lambda b, u: b.at[idx].set(u), bank, cohort)


@jax.jit
def broadcast_row0(bank, cohort):
    return jax.tree.map(
        lambda b, u: jnp.broadcast_to(u[0][None], b.shape).astype(b.dtype),
        bank, cohort)


class ClientBank:
    """Owns a per-client state pytree behind a residency backend."""

    def __init__(self, tree, *, n_clients: int, stacked: bool,
                 backend: str = "device", mesh=None,
                 chunk_rows: int = DEFAULT_CHUNK_ROWS, prefetch: bool = True):
        if backend not in BANK_BACKENDS:
            raise ValueError(
                f"unknown bank backend {backend!r}; known: {BANK_BACKENDS}")
        self.n_clients = int(n_clients)
        self.stacked = bool(stacked)
        # collapsed (single-copy) banks are O(1): residency is moot, the
        # device layout is always correct — requested backend is kept in
        # checkpoint meta by the caller, storage stays device-side
        self.backend = backend if self.stacked else "device"
        self.chunk_rows = int(chunk_rows)
        self.prefetch_enabled = bool(prefetch) and self.backend == "host"
        self._rec = obs.get_recorder()
        self._mesh = None
        self._shardings = None
        if self.backend == "sharded":
            from repro.launch.mesh import make_bank_mesh, n_client_shards

            self._mesh = mesh if mesh is not None else make_bank_mesh()
            shards = n_client_shards(self._mesh)
            if self.n_clients % shards:
                raise ValueError(
                    f"sharded bank: N={self.n_clients} not divisible by "
                    f"{shards} client shards (mesh {dict(self._mesh.shape)})")
        # host-backend async pipeline state
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pending: list = []
        self._staged: Optional[tuple] = None  # (t, idx, Future, bytes)
        self._lock = threading.Lock()
        # accounting
        self._gathered_bytes = 0
        self._staged_bytes = 0
        self._peak_device_bytes = 0
        self._slice_bytes = 0
        self._hits = 0
        self._misses = 0
        self._gather_wait_s = 0.0
        self._tree = self._ingest(tree)
        self._note_device_bytes()

    # -- storage ---------------------------------------------------------
    @property
    def tree(self):
        """The bank as stored: jax arrays (``device``/``sharded``) or
        numpy (``host``). Callers reading the host tree directly must
        ``flush()`` first if a round is in flight (``FedSimulator`` does
        this through ``state``)."""
        return self._tree

    def _ingest(self, tree):
        if self.backend == "host":
            # np.asarray of a jax array is a READ-ONLY device-buffer
            # view — the in-place scatter needs writable storage. Plain
            # writable numpy leaves (checkpoint restore, broadcast) pass
            # through zero-copy.
            def to_host(l):
                a = l if isinstance(l, np.ndarray) else np.asarray(l)
                return a if a.flags.writeable else a.copy()

            return jax.tree.map(to_host, tree)
        if self.backend == "sharded":
            return jax.tree.map(self._shard_put, tree)
        return jax.tree.map(jnp.asarray, tree)

    def _shard_put(self, leaf):
        from repro.launch.shardings import bank_sharding

        leaf = jnp.asarray(leaf)
        return jax.device_put(leaf, bank_sharding(self._mesh, leaf.ndim))

    def replace(self, tree) -> None:
        """Swap the bank's contents (set_cut re-partitions, collapsed
        per-round updates, checkpoint restore). Drains the pipeline
        first: a replace must observe every pending scatter."""
        self.flush()
        self._staged = None
        self._staged_bytes = 0
        self._tree = self._ingest(tree)
        self._note_device_bytes()

    # -- accounting ------------------------------------------------------
    @property
    def nbytes(self) -> int:
        return tree_nbytes(self._tree)

    @property
    def device_bytes(self) -> int:
        """Device-resident client-state bytes the bank holds NOW: the
        full tree (``device``), the per-process shards (``sharded``), or
        the staged + gathered K-slices (``host`` — the O(K) claim)."""
        if self.backend == "device":
            return self.nbytes
        if self.backend == "sharded":
            from repro.launch.mesh import n_client_shards

            return self.nbytes // n_client_shards(self._mesh)
        return self._gathered_bytes + self._staged_bytes

    def bytes_by_device(self) -> Dict[str, int]:
        """Bank bytes each device actually holds, read from the arrays'
        shards — where ``device_bytes`` assumes an even split, this shows
        whether the ``sharded`` rows really spread. Empty for ``host``."""
        out: Dict[str, int] = {}
        if self.backend == "host":
            return out
        for leaf in jax.tree.leaves(self._tree):
            for shard in leaf.addressable_shards:
                key = str(shard.device)
                out[key] = out.get(key, 0) + shard.data.nbytes
        return out

    def _note_device_bytes(self) -> None:
        self._peak_device_bytes = max(self._peak_device_bytes,
                                      self.device_bytes)

    def stats(self) -> Dict[str, Any]:
        return {
            "backend": self.backend,
            "bank_bytes": self.nbytes,
            "slice_bytes": self._slice_bytes,
            "device_bytes": self.device_bytes,
            "device_bytes_peak": max(self._peak_device_bytes,
                                     self.device_bytes),
            "bytes_by_device": self.bytes_by_device(),
            "prefetch_hits": self._hits,
            "prefetch_misses": self._misses,
            "gather_wait_s": self._gather_wait_s,
        }

    # -- worker (host backend) -------------------------------------------
    def _submit(self, fn, *args) -> Future:
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="bank")
        fut = self._pool.submit(fn, *args)
        with self._lock:
            self._pending.append(fut)
            # keep failed futures so flush() re-raises their exception
            self._pending = [f for f in self._pending
                             if not f.done() or f.exception() is not None]
        return fut

    def flush(self) -> None:
        """Block until every enqueued scatter/prefetch has completed
        (re-raising any worker exception). Whole-bank reads, ``replace``
        and ``save`` go through here."""
        with self._lock:
            pending, self._pending = self._pending, []
        for f in pending:
            f.result()

    def close(self) -> None:
        """Drain the pipeline and release the worker thread. Safe to
        call repeatedly; the bank stays readable afterwards, and a later
        scatter/prefetch lazily recreates the pool. Every run-owning
        caller (``FedSimulator.close``, ``train_lm``, fig11) closes its
        banks so worker threads don't accumulate across a sweep."""
        self.flush()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def __enter__(self) -> "ClientBank":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- round path ------------------------------------------------------
    def gather(self, idx, *, t: Optional[int] = None):
        """Device-resident cohort slice (leading axis K). ``idx=None`` is
        the identity cohort: the full bank (free on ``device``). On the
        ``host`` backend a staged prefetch for ``(t, idx)`` is consumed
        when present (span ``bank/wait`` covers how long the round still
        had to wait — ~0 when the overlap worked); otherwise the slice is
        copied synchronously after a pipeline flush."""
        with self._rec.span("bank/gather"):
            return self._gather(idx, t)

    def _gather(self, idx, t):
        if self.backend != "host":
            if idx is None:
                return self._tree
            # sharded: a cross-shard gather; the K-slice lands replicated
            return gather_rows(self._tree, jnp.asarray(idx))
        # host
        staged = self._staged
        if staged is not None and t is not None and staged[0] == t \
                and staged[1] is not None and idx is not None \
                and np.array_equal(staged[1], np.asarray(idx)):
            self._staged = None
            t0 = time.perf_counter()
            with self._rec.span("bank/wait"):
                out = staged[2].result()
            self._hits += 1
            self._gather_wait_s += time.perf_counter() - t0
            self._rec.counter("bank_prefetch_hit")
            self._gathered_bytes = staged[3]
            self._staged_bytes = 0
            self._note_device_bytes()
            return out
        self._misses += 1
        self._rec.counter("bank_prefetch_miss")
        self.flush()  # order after any pending scatter
        self._staged = None
        self._staged_bytes = 0
        out = self._slice_to_device(idx)
        self._gathered_bytes = tree_nbytes(out)
        self._slice_bytes = self._gathered_bytes
        self._note_device_bytes()
        return out

    def _slice_to_device(self, idx):
        if idx is None:
            return jax.tree.map(jnp.asarray, self._tree)
        idx = np.asarray(idx)
        return jax.tree.map(lambda b: jnp.asarray(b[idx]), self._tree)

    def prefetch(self, t: int, idx) -> None:
        """Stage round-``t``'s cohort slice host→device off the hot path
        (host backend only; no-op otherwise). The caller guarantees
        ordering vs in-flight scatters: enqueue BEFORE a pending scatter
        only when the two cohorts are disjoint."""
        if not self.prefetch_enabled or idx is None:
            return
        idx = np.asarray(idx)

        def stage():
            with self._rec.span("bank/prefetch"):
                return self._slice_to_device(idx)

        fut = self._submit(stage)
        nbytes = sum(
            int(np.prod((len(idx),) + l.shape[1:])) * np.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(self._tree))
        self._slice_bytes = max(self._slice_bytes, nbytes)
        self._staged = (int(t), idx, fut, nbytes)
        self._staged_bytes = nbytes
        self._note_device_bytes()

    def scatter(self, idx, updated, *, broadcast: bool = False) -> None:
        """Fold a trained cohort back into the bank. ``idx=None`` is the
        identity cohort (wholesale replace). ``broadcast=True`` writes
        cohort row 0 to every bank row (the client-aggregating schemes'
        model sync — inherently O(N)). Host-backend partial scatters are
        ASYNC: the device→host drain runs on the worker, ordered before
        any later prefetch/flush; duplicate cohort indices (the ρ
        sampler's with-replacement draws) resolve to the last occurrence
        on every backend. Wholesale and broadcast scatters invalidate
        any staged prefetch — they rewrite every row, so the staged
        slice is stale regardless of cohort disjointness."""
        with self._rec.span("bank/scatter"):
            self._scatter(idx, updated, broadcast)

    def _scatter(self, idx, updated, broadcast: bool) -> None:
        if self.backend != "host":
            if broadcast:
                new = broadcast_row0(self._tree, updated)
            elif idx is None:
                new = updated
            else:
                new = scatter_rows(self._tree, updated, jnp.asarray(idx))
            if self.backend == "sharded" and (broadcast or idx is not None):
                # pin the result back to the bank sharding (the compiled
                # scatter may leave the output replicated after a cross-
                # shard scatter); a no-op when already laid out right
                new = jax.tree.map(
                    lambda b, old: jax.device_put(b, old.sharding),
                    new, self._tree)
            self._tree = new
            self._note_device_bytes()
            return
        # host
        if broadcast or idx is None:
            self.flush()
            # wholesale/broadcast writes rewrite EVERY row, so a staged
            # prefetch — even for a disjoint cohort — is stale now.
            # Drop it: the next gather degrades to a miss and re-slices
            # the post-broadcast bank instead of serving old rows.
            self._staged = None
            self._staged_bytes = 0
            if broadcast:
                host = jax.tree.map(
                    lambda b, u: np.broadcast_to(
                        np.asarray(u[0])[None], b.shape).astype(
                            b.dtype, copy=True),
                    self._tree, updated)
            else:
                host = jax.tree.map(np.asarray, updated)
            self._tree = host
            return
        idx = np.asarray(idx)
        bank_leaves = jax.tree.leaves(self._tree)
        upd_leaves, _treedef = jax.tree.flatten(updated)

        def drain():
            with self._rec.span("bank/drain"):
                for b, u in zip(bank_leaves, upd_leaves):
                    b[idx] = np.asarray(u)  # blocks until the round computed u

        self._submit(drain)

    # -- whole-bank reductions (chunked through device) ------------------
    def full_device(self):
        """The whole bank on device — O(N) on purpose (drift metric when
        explicitly enabled, small-N debugging). Flushes first."""
        self.flush()
        return jax.tree.map(jnp.asarray, self._tree)

    def _chunks(self):
        n = self.n_clients
        step = max(1, self.chunk_rows)
        for s in range(0, n, step):
            yield s, min(n, s + step)

    def rho_mean(self, rho):
        """ρ-weighted mean over the bank axis → single-copy tree (the
        evaluation-time global model). One chunk ⇒ exactly
        ``jnp.sum(p * w, axis=0)`` on the full leaf — the pre-bank
        expression, bit for bit (always true on ``device``). Multiple
        chunks accumulate in float64 on the host and round ONCE, so the
        result stays within 1 ulp of the single-chunk expression (not
        bit-exact — DESIGN.md §15)."""
        if not self.stacked:
            return self._tree
        self.flush()
        rho = np.asarray(rho)
        if self.backend != "host" or self.n_clients <= self.chunk_rows:
            tree = self._tree if self.backend != "host" \
                else jax.tree.map(jnp.asarray, self._tree)
            return jax.tree.map(
                lambda p: jnp.sum(p * _reshape_w(rho, p), axis=0), tree)
        rho64 = rho.astype(np.float64)

        def part(p, s, e):
            w = rho64[s:e].reshape((-1,) + (1,) * (p.ndim - 1))
            return (np.asarray(p[s:e], np.float64) * w).sum(axis=0)

        acc = None
        for s, e in self._chunks():
            ps = jax.tree.map(lambda p: part(p, s, e), self._tree)
            acc = ps if acc is None else jax.tree.map(np.add, acc, ps)
        return jax.tree.map(
            lambda a, p: jnp.asarray(a.astype(np.asarray(p).dtype)),
            acc, self._tree)

    def merge_anchored(self, block, w):
        """Anchored-delta ρ-average of one bank block → single copy:
        ``anchor + Σ w (x − anchor)`` with row 0 as anchor — the same
        estimator as ``protocol.aggregate_cohort`` (bit-exact pass-
        through when all rows agree). One chunk ⇒ exactly
        ``aggregate_cohort(block, w, anchor=block[0])``. Multiple chunks
        accumulate the anchored deltas in float64 on the host and round
        ONCE — within 1 ulp of single-chunk, not bit-exact (DESIGN.md
        §15)."""
        from repro.core.protocol import aggregate_cohort

        self.flush()
        w = np.asarray(w)
        if self.backend != "host" or self.n_clients <= self.chunk_rows:
            blk = block if self.backend != "host" \
                else jax.tree.map(jnp.asarray, block)
            anchor = jax.tree.map(lambda p: p[0], blk)
            return aggregate_cohort(blk, jnp.asarray(w), anchor=anchor)
        anchor = jax.tree.map(lambda p: np.asarray(p[0], np.float64), block)
        w64 = w.astype(np.float64)

        def part(p, a, s, e):
            wb = w64[s:e].reshape((-1,) + (1,) * (p.ndim - 1))
            return ((np.asarray(p[s:e], np.float64) - a[None]) * wb).sum(0)

        upd = None
        for s, e in self._chunks():
            ps = jax.tree.map(lambda p, a: part(p, a, s, e), block, anchor)
            upd = ps if upd is None else jax.tree.map(np.add, upd, ps)
        return jax.tree.map(
            lambda p, a, u: jnp.asarray(
                (a + u).astype(np.asarray(p).dtype)),
            block, anchor, upd)

    def broadcast_single(self, single):
        """A single-copy block stacked to ``(N, ...)`` in this backend's
        storage (``set_cut`` moving boundary layers client-ward)."""
        n = self.n_clients
        if self.backend == "host":
            return jax.tree.map(
                lambda x: np.broadcast_to(
                    np.asarray(x)[None], (n,) + x.shape).astype(
                        x.dtype, copy=True), single)
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape) + 0.0, single)
        if self.backend == "sharded":
            stacked = jax.tree.map(self._shard_put, stacked)
        return stacked

    def drift(self, drift_fn) -> float:
        """Γ drift proxy over the FULL bank via ``drift_fn`` (the jitted
        ``ProtocolEngine.client_drift``). Device/sharded banks evaluate
        in place; the host bank pays one O(N) host→device copy — the
        bit-parity form ``SimConfig.drift_metric=True`` selects. The
        auto default streams instead (``drift_streamed``)."""
        if not self.stacked:
            return 0.0
        if self.backend == "host":
            return float(drift_fn(self.full_device()))
        return float(drift_fn(self._tree))

    def drift_streamed(self) -> float:
        """Γ chunk-streamed through the bank surface: per leaf,
        Σ_n‖p_n − mean‖² = Σ_n‖p_n‖² − ‖Σ_n p_n‖²/N, accumulated in
        float64 over ``chunk_rows`` slices — the host bank never
        materializes on device, so Γ costs no device memory at all.
        Algebraically equal to ``drift``; the two-pass-free form trades
        bit-exactness for streaming (catastrophic cancellation is
        bounded by clamping at 0), which is why the bit-parity tests pin
        ``drift`` and the host default reports this one (DESIGN.md
        §15)."""
        if not self.stacked:
            return 0.0
        self.flush()
        n = self.n_clients
        total = 0.0
        for p in jax.tree.leaves(self._tree):
            s1, s2 = 0.0, 0.0
            for s, e in self._chunks():
                c = np.asarray(p[s:e], np.float64)
                s1 = s1 + c.sum(axis=0)
                s2 = s2 + float(np.square(c).sum())
            total += s2 - float(np.square(s1).sum()) / n
        return max(total, 0.0)
