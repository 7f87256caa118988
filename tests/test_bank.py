"""Client-bank backends (DESIGN.md §15): the O(N) per-client state
behind interchangeable residency backends.

Pins the tentpole contracts:

* ``device`` / ``host`` / ``sharded`` backends are BIT-IDENTICAL over a
  multi-round run for all four schemes — including a ``set_cut``
  migration and a K<N cohort — so residency is a pure performance
  choice, never a semantics one;
* the host backend's double-buffered prefetch changes nothing about the
  results (prefetch on/off parity) while keeping peak device-resident
  client-state bytes within 2× the K-slice — the O(K) claim fig11's
  scale gate enforces;
* whole-bank reductions (ρ-mean, anchored merge) chunk through device
  and stay numerically faithful when ``chunk_rows < N``;
* duplicate cohort indices (the ρ sampler's with-replacement draws)
  resolve identically on every backend;
* ``CyclicPartition`` provides the O(1)-memory partition surface the
  N=1M sweep needs.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs.paper_cnn import LIGHT_CONFIG  # noqa: E402
from repro.core.bank import (BANK_BACKENDS, ClientBank,  # noqa: E402
                             tree_nbytes)
from repro.core.simulator import FedSimulator, SimConfig  # noqa: E402

N, K, BATCH = 6, 3, 8


def _rho(n, seed=0):
    r = np.random.RandomState(seed).rand(n).astype(np.float64) + 0.5
    return (r / r.sum()).astype(np.float32)


def _data(k, tau=1, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.rand(k, tau, BATCH, 28, 28, 1).astype(np.float32),
            rng.randint(0, 10, (k, tau, BATCH)))


def _sim(scheme="sfl_ga", cut=2, cohort=K, sampler="uniform",
         bank="device", rho=None, **kw):
    # drift_metric=True everywhere: the host default (off → NaN) would
    # make metric-dict comparison vacuous for the drifting schemes
    return FedSimulator(
        LIGHT_CONFIG,
        SimConfig(scheme=scheme, cut=cut, n_clients=N, batch=BATCH,
                  cohort=cohort, sampler=sampler, bank=bank,
                  drift_metric=True, **kw),
        rho=rho, seed=0)


def _run(sim, rounds=3, migrate_at=None, new_cut=1):
    out = []
    for r in range(rounds):
        if migrate_at is not None and r == migrate_at:
            sim.set_cut(new_cut)
        out.append(sim.run_round(*_data(sim.n_participants, seed=r)))
    return out


def _assert_state_equal(a, b):
    la, lb = jax.tree.leaves(a.state), jax.tree.leaves(b.state)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------- parity
class TestBackendParity:
    @pytest.mark.parametrize("scheme", ["sfl_ga", "sfl", "psl", "fl"])
    @pytest.mark.parametrize("backend", ["host", "sharded"])
    def test_bitidentical_with_migration(self, scheme, backend):
        """device vs host vs sharded: same rounds, same set_cut
        migration, same K<N cohort → identical metrics AND state."""
        rho = _rho(N, seed=4)
        cut = 2 if scheme != "fl" else 1
        mig = 1 if scheme != "fl" else None  # fl never re-partitions
        ref = _sim(scheme, cut=cut, rho=rho)
        alt = _sim(scheme, cut=cut, rho=rho, bank=backend)
        ma = _run(ref, migrate_at=mig)
        mb = _run(alt, migrate_at=mig)
        assert ma == mb
        _assert_state_equal(ref, alt)
        np.testing.assert_array_equal(
            np.asarray(jax.tree.leaves(ref.global_params())[0]),
            np.asarray(jax.tree.leaves(alt.global_params())[0]))

    def test_identity_cohort_parity(self):
        """Full participation (identity cohort): the host backend's
        wholesale gather/scatter path."""
        ref = _sim(cohort=None, sampler="full")
        alt = _sim(cohort=None, sampler="full", bank="host")
        assert _run(ref, migrate_at=2, new_cut=3) == \
            _run(alt, migrate_at=2, new_cut=3)
        _assert_state_equal(ref, alt)

    def test_rho_sampler_duplicate_scatter_parity(self):
        """ρ sampling draws WITH replacement — duplicate cohort indices
        must resolve identically (last occurrence) on every backend."""
        rho = _rho(N, seed=1)
        ref = _sim(sampler="rho", rho=rho)
        host = _sim(sampler="rho", rho=rho, bank="host")
        # make sure the schedule actually exercises a duplicate draw
        dup = any(len(set(ref.cohort_for_round(t)[0].tolist())) < K
                  for t in range(4))
        assert dup, "seed produced no duplicate draws; pick another"
        assert _run(ref, rounds=4) == _run(host, rounds=4)
        _assert_state_equal(ref, host)

    def test_prefetch_off_parity(self):
        """The double-buffer is invisible to results: prefetch on/off
        runs are bit-identical, and the on-run actually overlapped."""
        on = _sim(bank="host")
        off = _sim(bank="host", bank_prefetch=False)
        assert _run(on, rounds=5) == _run(off, rounds=5)
        _assert_state_equal(on, off)
        st_on, st_off = on.bank.stats(), off.bank.stats()
        assert st_on["prefetch_hits"] > 0
        assert st_off["prefetch_hits"] == 0

    def test_collapsed_bank_forces_device(self):
        """sfl/fl banks are ONE copy — O(1), so residency is moot and
        the bank stays device-side whatever was requested."""
        sim = _sim("sfl", bank="host")
        assert sim.bank.backend == "device"
        assert not sim.bank.stacked


# ------------------------------------------------------------ O(K) budget
class TestDeviceBudget:
    def test_host_peak_within_two_slices(self):
        """The fig11 acceptance bar at test scale: peak device-resident
        client-state ≤ 2× the K-slice (in-flight + staged prefetch)."""
        sim = _sim(bank="host")
        _run(sim, rounds=5)
        sim.bank.flush()
        st = sim.bank.stats()
        slice_bytes = st["bank_bytes"] // N * K
        assert 0 < st["device_bytes_peak"] <= 2 * slice_bytes
        assert st["bank_bytes"] == tree_nbytes(sim.state["client"])

    def test_host_bank_stores_numpy(self):
        sim = _sim(bank="host")
        _run(sim, rounds=2)
        for leaf in jax.tree.leaves(sim.state["client"]):
            assert isinstance(leaf, np.ndarray)
        for leaf in jax.tree.leaves(sim.state["server"]):
            assert not isinstance(leaf, np.ndarray)  # server stays on device


# ------------------------------------------------------- bank unit surface
class TestClientBankUnit:
    def _tree(self, n=5, d=4, seed=0):
        rng = np.random.RandomState(seed)
        return {"w": rng.randn(n, d).astype(np.float32),
                "b": rng.randn(n).astype(np.float32)}

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown bank backend"):
            ClientBank(self._tree(), n_clients=5, stacked=True,
                       backend="tpu_pod")
        assert BANK_BACKENDS == ("device", "host", "sharded")

    def test_gather_scatter_roundtrip_host(self):
        t = self._tree()
        orig = jax.tree.map(np.copy, t)  # host ingest is zero-copy: the
        bank = ClientBank(t, n_clients=5, stacked=True, backend="host")
        idx = np.asarray([1, 3])         # bank aliases t's numpy leaves
        got = bank.gather(idx, t=0)
        np.testing.assert_array_equal(np.asarray(got["w"]), orig["w"][idx])
        upd = jax.tree.map(lambda x: x + 1.0, got)
        bank.scatter(idx, upd)
        bank.flush()
        np.testing.assert_array_equal(bank.tree["w"][idx], orig["w"][idx] + 1)
        np.testing.assert_array_equal(bank.tree["w"][0], orig["w"][0])

    def test_prefetch_hit_and_miss_accounting(self):
        bank = ClientBank(self._tree(), n_clients=5, stacked=True,
                          backend="host")
        bank.prefetch(7, [0, 2])
        got = bank.gather([0, 2], t=7)  # consumes the staged slice
        st = bank.stats()
        assert (st["prefetch_hits"], st["prefetch_misses"]) == (1, 0)
        np.testing.assert_array_equal(np.asarray(got["b"]),
                                      bank.tree["b"][[0, 2]])
        bank.gather([1, 4], t=8)  # nothing staged → miss
        assert bank.stats()["prefetch_misses"] == 1

    def test_stale_prefetch_not_consumed(self):
        """A staged slice for the WRONG (t, idx) must be discarded, not
        served — the ordering contract, not a cache."""
        bank = ClientBank(self._tree(), n_clients=5, stacked=True,
                          backend="host")
        bank.prefetch(3, [0, 1])
        got = bank.gather([0, 2], t=3)  # different cohort
        assert bank.stats()["prefetch_misses"] == 1
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      bank.tree["w"][[0, 2]])

    def test_broadcast_scatter_host(self):
        t = self._tree()
        bank = ClientBank(t, n_clients=5, stacked=True, backend="host")
        upd = {"w": jnp.ones((2, 4)) * 9, "b": jnp.ones((2,)) * 9}
        bank.scatter([1, 3], upd, broadcast=True)
        np.testing.assert_array_equal(bank.tree["w"],
                                      np.full((5, 4), 9, np.float32))

    def test_broadcast_scatter_invalidates_staged_prefetch(self):
        """A broadcast scatter rewrites EVERY bank row — a prefetch
        staged earlier (even for a disjoint cohort) is stale and must
        not be served: the next gather has to return broadcast rows."""
        bank = ClientBank(self._tree(), n_clients=5, stacked=True,
                          backend="host")
        bank.prefetch(1, [2, 3])  # disjoint from the scattering cohort
        upd = {"w": jnp.full((2, 4), 100.0), "b": jnp.full((2,), 100.0)}
        bank.scatter([0, 1], upd, broadcast=True)
        got = bank.gather([2, 3], t=1)  # must miss, not consume stale rows
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.full((2, 4), 100, np.float32))
        st = bank.stats()
        assert (st["prefetch_hits"], st["prefetch_misses"]) == (0, 1)

    def test_wholesale_scatter_invalidates_staged_prefetch(self):
        """Same contract for the idx=None (identity cohort) scatter."""
        bank = ClientBank(self._tree(), n_clients=5, stacked=True,
                          backend="host")
        bank.prefetch(1, [2, 3])
        new = {"w": np.full((5, 4), 7, np.float32),
               "b": np.full((5,), 7, np.float32)}
        bank.scatter(None, new)
        got = bank.gather([2, 3], t=1)
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.full((2, 4), 7, np.float32))
        assert bank.stats()["prefetch_misses"] == 1

    def test_close_releases_worker_and_stays_usable(self):
        """close() (and the context-manager form) drains + shuts down
        the worker pool; the bank stays readable, and a later scatter
        lazily restarts the worker so close is safe to call mid-sweep."""
        with ClientBank(self._tree(), n_clients=5, stacked=True,
                        backend="host") as bank:
            idx = [0, 2]
            upd = jax.tree.map(lambda x: x + 1.0, bank.gather(idx, t=0))
            bank.scatter(idx, upd)
        assert bank._pool is None  # exited the with: worker released
        before = np.copy(bank.tree["w"])
        bank.scatter([1], jax.tree.map(lambda x: x[:1] * 0, bank.gather([1])))
        bank.close()
        assert bank._pool is None and bank.tree["w"][1, 0] == 0.0
        np.testing.assert_array_equal(bank.tree["w"][0], before[0])

    def test_chunked_rho_mean_matches_unchunked(self):
        t = self._tree(n=7)
        rho = _rho(7, seed=3)
        whole = ClientBank(t, n_clients=7, stacked=True, backend="host")
        chunked = ClientBank(t, n_clients=7, stacked=True, backend="host",
                             chunk_rows=2)
        a = whole.rho_mean(rho)
        b = chunked.rho_mean(rho)
        np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b["w"]),
                                   rtol=1e-6, atol=1e-7)
        ref = np.einsum("n,nd->d", rho.astype(np.float64),
                        t["w"].astype(np.float64))
        np.testing.assert_allclose(np.asarray(a["w"]), ref, rtol=1e-5)

    def test_chunked_merge_anchored_matches_unchunked(self):
        t = self._tree(n=7, seed=5)
        w = _rho(7, seed=6)
        whole = ClientBank(t, n_clients=7, stacked=True, backend="host")
        chunked = ClientBank(t, n_clients=7, stacked=True, backend="host",
                             chunk_rows=3)
        a = whole.merge_anchored(t, w)
        b = chunked.merge_anchored(t, w)
        np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b["w"]),
                                   rtol=1e-6, atol=1e-7)

    def test_broadcast_single_is_writable_per_row(self):
        bank = ClientBank([], n_clients=4, stacked=True, backend="host")
        stacked = bank.broadcast_single({"w": jnp.ones((3,))})
        stacked["w"][2] = 7.0  # np.broadcast_to views would raise here
        assert stacked["w"][0, 0] == 1.0 and stacked["w"][2, 0] == 7.0

    def test_sharded_roundtrip_matches_device(self):
        t = self._tree(n=4)
        dev = ClientBank(t, n_clients=4, stacked=True, backend="device")
        sh = ClientBank(t, n_clients=4, stacked=True, backend="sharded")
        idx = [0, 3]
        upd = jax.tree.map(lambda x: x * 2.0, dev.gather(idx))
        dev.scatter(idx, upd)
        sh.scatter(idx, jax.tree.map(lambda x: x * 2.0, sh.gather(idx)))
        np.testing.assert_array_equal(np.asarray(dev.tree["w"]),
                                      np.asarray(sh.tree["w"]))

    def test_sharded_rows_spread_over_devices(self):
        """On four (virtual CPU) devices the sharded bank holds a quarter
        of its rows on each, and a cross-shard gather/scatter round trip
        still matches the device bank. Runs in a child process: the
        device count is fixed when JAX starts."""
        import os
        import subprocess
        import sys
        import textwrap

        script = textwrap.dedent("""
            import jax, numpy as np
            from repro.core.bank import ClientBank
            assert len(jax.devices()) == 4
            rng = np.random.RandomState(0)
            t = {"w": rng.randn(8, 6).astype(np.float32),
                 "b": rng.randn(8).astype(np.float32)}
            dev = ClientBank(t, n_clients=8, stacked=True, backend="device")
            sh = ClientBank(t, n_clients=8, stacked=True, backend="sharded")
            per = sh.stats()["bytes_by_device"]
            assert len(per) == 4, per
            assert set(per.values()) == {sh.nbytes // 4}, per
            assert dev.stats()["bytes_by_device"] == {
                str(jax.devices()[0]): dev.nbytes}
            idx = [1, 6]
            dev.scatter(idx, jax.tree.map(lambda x: x * 2.0, dev.gather(idx)))
            sh.scatter(idx, jax.tree.map(lambda x: x * 2.0, sh.gather(idx)))
            np.testing.assert_array_equal(np.asarray(dev.tree["w"]),
                                          np.asarray(sh.tree["w"]))
            assert len(sh.tree["w"].sharding.device_set) == 4
        """)
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-3000:]


# --------------------------------------------------------- cyclic partition
class TestCyclicPartition:
    def test_surface_and_wrap(self):
        from repro.data.federated import CyclicPartition

        p = CyclicPartition(10, 4)  # part_size = 2
        assert len(p) == 4 and p.part_size == 2
        np.testing.assert_array_equal(p[0], [0, 1])
        np.testing.assert_array_equal(p[3], [6, 7])
        np.testing.assert_array_equal(p[-1], [6, 7])
        big = CyclicPartition(10, 4, part_size=6)
        np.testing.assert_array_equal(big[1], [6, 7, 8, 9, 0, 1])  # wraps
        with pytest.raises(IndexError):
            p[4]
        with pytest.raises(ValueError):
            CyclicPartition(0, 4)

    def test_huge_n_is_lazy(self):
        from repro.data.federated import CyclicPartition

        p = CyclicPartition(4096, 1_000_000)
        assert len(p) == 1_000_000
        assert p[999_999].shape == (1,)  # no O(N) state materialized

    def test_replacement_fraction_fast_path(self):
        from repro.data.federated import (CyclicPartition,
                                          replacement_fraction)

        assert replacement_fraction(CyclicPartition(100, 10), 8) == 0.0
        assert replacement_fraction(CyclicPartition(100, 10), 16) == 1.0

    def test_round_batches_with_cyclic(self):
        from repro.data.federated import round_batches
        from repro.data.synthetic import make_image_dataset

        ds = make_image_dataset("mnist", n=64, seed=0)
        from repro.data.federated import CyclicPartition

        parts = CyclicPartition(64, 16)
        xs, ys = round_batches(ds, parts, 4, 1, np.random.RandomState(0),
                               idx=[0, 7, 15])
        assert xs.shape[:3] == (3, 1, 4) and ys.shape == (3, 1, 4)


# ------------------------------------------------------------- obs wiring
class TestBankObs:
    def test_round_events_carry_bank_stats(self):
        from repro import obs

        rec = obs.Recorder()
        with obs.use_recorder(rec):
            sim = _sim(bank="host")
            _run(sim, rounds=2)
        rounds = [e for e in rec.events if e.get("kind") == "round"]
        assert rounds and all("bank" in e for e in rounds)
        assert rounds[-1]["bank"]["backend"] == "host"
        assert rounds[-1]["bank"]["device_bytes_peak"] > 0
        hits = [e for e in rec.events
                if e.get("kind") == "counter"
                and e.get("name") == "bank_prefetch_hit"]
        assert hits  # the overlap actually engaged under obs

    def test_report_renders_bank_section(self):
        from repro import obs
        from repro.obs.report import render_report

        rec = obs.Recorder()
        with obs.use_recorder(rec):
            sim = _sim(bank="host")
            _run(sim, rounds=2)
        text, bad = render_report(rec.events)
        assert "== client bank ==" in text
        assert "host" in text and bad == 0


# ------------------------------------------------- multi-chunk numerics
class TestMultiChunkNumerics:
    """PR 7 residue, pinned instead of folklore (DESIGN.md §15): when a
    whole-bank reduction spans MULTIPLE chunks it accumulates in float64
    and rounds once, so it stays within 1 ulp of the exact single-chunk
    expression. Bit-exactness with the device path is NOT promised there
    — float32 summation order differs — which is why the parity tests
    pin the single-chunk form and this one pins the ulp bound."""

    BIG = 70_000  # > DEFAULT_CHUNK_ROWS=65536 → two chunks

    def _bank(self, t):
        return ClientBank(jax.tree.map(np.copy, t), n_clients=self.BIG,
                          stacked=True, backend="host")

    def _tree(self):
        rng = np.random.RandomState(0)
        return {"w": rng.randn(self.BIG, 3).astype(np.float32),
                "b": rng.randn(self.BIG).astype(np.float32)}

    @staticmethod
    def _assert_ulp(ref, got, bound=1.0):
        ref, got = np.asarray(ref), np.asarray(got)
        ulp = np.abs(ref - got) / np.spacing(np.abs(ref))
        assert np.max(ulp) <= bound, f"max ulp {np.max(ulp)}"

    def test_rho_mean_within_one_ulp(self):
        t = self._tree()
        bank = self._bank(t)
        assert len(list(bank._chunks())) == 2
        rho = np.random.RandomState(3).rand(self.BIG) + 0.5
        rho = (rho / rho.sum()).astype(np.float32)
        got = bank.rho_mean(rho)
        r64 = rho.astype(np.float64)
        for k in t:
            ref = np.einsum("n...,n->...", t[k].astype(np.float64),
                            r64).astype(np.float32)
            self._assert_ulp(ref, got[k])

    def test_merge_anchored_within_one_ulp(self):
        t = self._tree()
        bank = self._bank(t)
        w = np.random.RandomState(4).rand(self.BIG).astype(np.float64)
        w = (w / w.sum()).astype(np.float32)
        got = bank.merge_anchored(t, w)
        w64 = w.astype(np.float64)
        for k in t:
            a64 = t[k][0].astype(np.float64)
            ref = (a64 + np.einsum(
                "n...,n->...", t[k].astype(np.float64) - a64[None],
                w64)).astype(np.float32)
            self._assert_ulp(ref, got[k])

    def test_single_chunk_stays_bit_exact_with_device(self):
        """chunk_rows ≥ N keeps the literal f32 device expression — the
        bit-parity contract the backend-parity tests rely on."""
        rng = np.random.RandomState(1)
        t = {"w": rng.randn(50, 3).astype(np.float32)}
        rho = _rho(50, seed=2)
        host = ClientBank(jax.tree.map(np.copy, t), n_clients=50,
                          stacked=True, backend="host")
        dev = ClientBank(jax.tree.map(np.copy, t), n_clients=50,
                         stacked=True, backend="device")
        np.testing.assert_array_equal(np.asarray(host.rho_mean(rho)["w"]),
                                      np.asarray(dev.rho_mean(rho)["w"]))


# ------------------------------------------------------- streamed drift
class TestDriftStreamed:
    """PR 7 residue: Γ chunk-streamed through the bank surface, so the
    host backend's drift metric is a number again instead of NaN."""

    def test_matches_exact_form(self):
        from repro.core.protocol import ProtocolEngine

        rng = np.random.RandomState(7)
        t = {"w": rng.randn(9, 4).astype(np.float32)}
        bank = ClientBank(jax.tree.map(np.copy, t), n_clients=9,
                          stacked=True, backend="host", chunk_rows=2)
        exact = float(jax.jit(ProtocolEngine.client_drift)(
            jax.tree.map(jnp.asarray, t)))
        assert exact > 0
        np.testing.assert_allclose(bank.drift_streamed(), exact, rtol=1e-5)

    def test_collapsed_bank_is_zero(self):
        bank = ClientBank({"w": np.zeros((3,), np.float32)}, n_clients=4,
                          stacked=False, backend="host")
        assert bank.drift_streamed() == 0.0

    @pytest.mark.parametrize("bank_backend", ["host", "sharded"])
    def test_sim_default_reports_finite_drift(self, bank_backend):
        """drift_metric=None (the default): host streams, sharded keeps
        the in-place exact form — neither reports NaN for the drifting
        schemes any more."""
        ref = _sim(bank="device")  # exact, device
        sim = FedSimulator(
            LIGHT_CONFIG,
            SimConfig(scheme="sfl_ga", cut=2, n_clients=N, batch=BATCH,
                      cohort=K, sampler="uniform", bank=bank_backend),
            seed=0)
        for r in range(2):
            me = ref.run_round(*_data(K, seed=r))
            ms = sim.run_round(*_data(K, seed=r))
            assert np.isfinite(ms["client_drift"])
            np.testing.assert_allclose(ms["client_drift"],
                                       me["client_drift"], rtol=1e-4)
        ref.close(), sim.close()

    def test_drift_metric_false_still_off(self):
        sim = FedSimulator(
            LIGHT_CONFIG,
            SimConfig(scheme="sfl_ga", cut=2, n_clients=N, batch=BATCH,
                      cohort=K, sampler="uniform", bank="host",
                      drift_metric=False), seed=0)
        assert np.isnan(sim.run_round(*_data(K, seed=0))["client_drift"])
        sim.close()
