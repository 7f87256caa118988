"""Event-driven buffered-async round engine (DESIGN.md §16).

Pins the tentpole contracts of ``core.async_engine``:

* **sync is the degenerate case** — with B = K and a zero-spread
  completion draw the engine routes every step through the UNCHANGED
  synchronous round: metrics and state stay bit-identical to the
  ``run_round`` barrier loop on all four schemes;
* the genuinely async path (B < K, heterogeneous completion times)
  advances a virtual clock, reports non-zero staleness, keeps the
  in-flight queue topped up, and ``drain()`` empties it;
* async runs are bit-identical across bank backends (residency stays a
  pure performance choice, exactly as in the sync loop);
* the obs ledger reconciles async traffic EXACTLY: per merge, measured
  tap bits equal the modeled ``round_traffic_breakdown`` split
  (compute legs at each dispatched generation's size, model-sync uplink
  at the merge size);
* ``AdmissionSampler`` degenerates to the base sampler's per-round
  schedule when ``refill == K`` and stays pure in ``(seed, d)``;
* ``protocol.merge_async`` applies the (1+τ)^(−λ) staleness discount to
  deltas only (λ(0) = 1: fresh entries merge at full weight).
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs.paper_cnn import LIGHT_CONFIG  # noqa: E402
from repro.core.cohort import AdmissionSampler, make_sampler  # noqa: E402
from repro.core.protocol import (merge_async,  # noqa: E402
                                 staleness_discount)
from repro.core.simulator import FedSimulator, SimConfig  # noqa: E402
from repro.obs.recorder import Recorder  # noqa: E402
from repro.sysmodel.latency import (completion_time_fn,  # noqa: E402
                                    constant_completion_fn)

SCHEMES = ["sfl_ga", "sfl", "psl", "fl"]
N, K, BATCH = 6, 3, 8


def _data(k, tau=1, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.rand(k, tau, BATCH, 28, 28, 1).astype(np.float32),
            rng.randint(0, 10, (k, tau, BATCH)))


def _data_fn(tau=1):
    return lambda d, idx: _data(len(idx), tau=tau, seed=d)


def _sim(scheme="sfl_ga", bank="device", tau=1, **kw):
    return FedSimulator(
        LIGHT_CONFIG,
        SimConfig(scheme=scheme, cut=2, n_clients=N, batch=BATCH, tau=tau,
                  cohort=K, sampler="uniform", bank=bank,
                  drift_metric=True, **kw),
        seed=0)


def _metrics_equal(ma, mb, ctx=""):
    assert set(ma) <= set(mb), (ctx, ma, mb)
    for k, va in ma.items():
        vb = mb[k]
        ok = va == vb or (isinstance(va, float)
                          and np.isnan(va) and np.isnan(vb))
        assert ok, f"{ctx}: {k}: {va} != {vb}"


def _state_equal(a, b):
    la, lb = jax.tree.leaves(a.state), jax.tree.leaves(b.state)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------------ sync parity
class TestSyncParity:
    """The barrier loop must stay reachable, bit for bit, as the
    degenerate B=K / zero-spread schedule — the refactor's safety net."""

    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_bitidentical_to_run_round(self, scheme):
        a, b = _sim(scheme), _sim(scheme)
        eng = b.async_engine(_data_fn(),
                             completion_fn=constant_completion_fn(N, 1.0))
        for t in range(3):
            ma = a.run_round(*_data(K, seed=t))
            mb = eng.step()
            _metrics_equal(ma, mb, f"{scheme} round {t}")
            assert mb["staleness_mean"] == 0.0
            assert mb["queue_depth"] == 0
        _state_equal(a, b)
        assert eng.sync_steps == 3
        assert eng.clock == 3.0  # constant unit completion time
        a.close(), b.close()

    def test_sync_path_closes_after_async_dispatch(self):
        """Once any step dispatches asynchronously the round counter
        decouples from the generation index — the degenerate fast path
        must stay off even if later draws look degenerate."""
        sim = _sim()

        def completion(d):
            # generation 0 spreads, everything after looks degenerate
            return np.linspace(1.0, 5.0, N) if d == 0 else np.full(N, 1.0)

        eng = sim.async_engine(_data_fn(), buffer=K,
                               completion_fn=completion)
        for _ in range(3):
            eng.step()
        assert eng.sync_steps == 0
        sim.close()

    def test_multi_epoch_parity(self):
        a, b = _sim(tau=2), _sim(tau=2)
        eng = b.async_engine(_data_fn(tau=2),
                             completion_fn=constant_completion_fn(N, 2.5))
        for t in range(2):
            _metrics_equal(a.run_round(*_data(K, tau=2, seed=t)),
                           eng.step(), f"tau=2 round {t}")
        _state_equal(a, b)
        a.close(), b.close()


# ------------------------------------------------------------ async path
class TestAsyncEngine:
    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_buffered_async_runs(self, scheme):
        sim = _sim(scheme)
        eng = sim.async_engine(_data_fn(), buffer=2, straggler_factor=8.0)
        outs = [eng.step() for _ in range(5)]
        assert eng.sync_steps == 0
        assert all(o["merged"] == 2 for o in outs)
        # heterogeneous completion times force out-of-generation merges
        assert any(o["staleness_mean"] > 0 for o in outs)
        # virtual clock only moves forward
        clocks = [o["clock"] for o in outs]
        assert clocks == sorted(clocks) and clocks[0] > 0
        # each step refills to K then merges B: K−B stay in flight
        assert eng.queue_depth == K - 2
        rest = eng.drain()
        assert eng.queue_depth == 0
        assert sum(o["merged"] for o in rest) == K - 2
        sim.close()

    def test_buffer_validation(self):
        sim = _sim()
        with pytest.raises(ValueError, match="buffer"):
            sim.async_engine(_data_fn(), buffer=K + 1)
        with pytest.raises(ValueError, match="outside"):
            sim.async_engine(_data_fn(), buffer=0)
        sim.close()

    def test_merge_order_deterministic(self):
        """Same seeds → the identical merge schedule (virtual-time ties
        break on (client, gen), never on list order)."""
        runs = []
        for _ in range(2):
            sim = _sim()
            eng = sim.async_engine(_data_fn(), buffer=1,
                                   completion_fn=constant_completion_fn(
                                       N, 1.0))
            eng._sync_ok = False  # force the event path despite B=1...
            outs = [eng.step() for _ in range(6)]
            runs.append([(o["merge_idx"], o["clock"], o["staleness_mean"])
                         for o in outs])
            sim.close()
        assert runs[0] == runs[1]

    @pytest.mark.parametrize("scheme", SCHEMES)
    @pytest.mark.parametrize("backend", ["host", "sharded"])
    def test_cross_bank_parity(self, scheme, backend):
        """Async runs are bit-identical across bank backends — residency
        stays a pure performance choice under the event engine too."""

        def run(bank):
            sim = _sim(scheme, bank=bank)
            eng = sim.async_engine(_data_fn(), buffer=2,
                                   straggler_factor=8.0)
            outs = [eng.step() for _ in range(4)]
            leaves = [np.asarray(x) for x in jax.tree.leaves(sim.state)]
            sim.close()
            return outs, leaves

        oa, la = run("device")
        ob, lb = run(backend)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(x, y)
        for ma, mb in zip(oa, ob):
            _metrics_equal(ma, mb, f"{scheme}/{backend}")

    def test_completion_time_fn_straggler_spread(self):
        fn = completion_time_fn(32, seed=7, straggler_factor=4.0)
        t0 = fn(0)
        assert t0.shape == (32,) and (t0 > 0).all()
        # the straggler multiplier dominates the channel draw: the
        # spread widens with the factor and stays well above flat
        assert t0.max() / t0.min() >= 2.0
        flat = completion_time_fn(32, seed=7, straggler_factor=1.0)(0)
        wide = completion_time_fn(32, seed=7, straggler_factor=16.0)(0)
        assert (wide.max() / wide.min()) > (t0.max() / t0.min()) \
            > (flat.max() / flat.min())
        # pure in (seed, t): same round → same draw, rounds decorrelate
        np.testing.assert_array_equal(t0, fn(0))
        assert not np.array_equal(t0, fn(1))


# --------------------------------------------------------- reconciliation
class TestAsyncTraffic:
    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_measured_equals_modeled(self, scheme):
        """Per merge, ledger tap bits reconcile EXACTLY against the
        dispatch/merge split of ``round_traffic_breakdown`` — the same
        zero-tolerance gate the synchronous rounds pass."""
        rec = Recorder()
        with obs.use_recorder(rec):
            sim = _sim(scheme, tau=2)
            eng = sim.async_engine(_data_fn(tau=2), buffer=2,
                                   straggler_factor=8.0)
            for _ in range(4):
                eng.step()
            eng.drain()
        ev = [e for e in rec.events if e.get("kind") == "traffic"]
        assert len(ev) >= 5
        for e in ev:
            assert e["name"] == "async_traffic"
            assert e["measured"] == e["modeled"], e
        merges = [e for e in rec.events if e.get("kind") == "async"]
        assert len(merges) == len(ev)
        assert all(m["queue_depth"] >= 0 for m in merges)
        sim.close()

    def test_gauges_emitted(self):
        rec = Recorder()
        with obs.use_recorder(rec):
            sim = _sim()
            eng = sim.async_engine(_data_fn(), buffer=2,
                                   straggler_factor=8.0)
            for _ in range(3):
                eng.step()
        names = {e.get("name") for e in rec.events
                 if e.get("kind") == "gauge"}
        assert {"async_queue_depth", "async_staleness"} <= names
        sim.close()


# ------------------------------------------------------------- admission
class TestAdmissionSampler:
    def test_degenerate_refill_is_base_schedule(self):
        base = make_sampler("uniform", N, K, seed=11)
        adm = AdmissionSampler(base)
        for d in range(4):
            ia, wa = adm.admit(d)
            ib, wb = base.cohort(d)
            np.testing.assert_array_equal(ia, ib)
            np.testing.assert_array_equal(wa, wb)

    def test_refill_size_and_purity(self):
        base = make_sampler("uniform", N, K, seed=5)
        adm = AdmissionSampler(base, refill=2)
        i0, _ = adm.admit(0)
        assert i0.size == K  # initial in-flight set is the sync cohort
        for d in (1, 2, 3):
            idx, w = adm.admit(d)
            assert idx.size == 2 and w.shape == (2,)
            np.testing.assert_array_equal(idx, adm.admit(d)[0])  # pure

    def test_full_base_falls_back_to_uniform_refills(self):
        base = make_sampler("full", N, seed=5)
        adm = AdmissionSampler(base, refill=2)
        np.testing.assert_array_equal(adm.admit(0)[0], np.arange(N))
        idx, _ = adm.admit(1)
        assert idx.size == 2 and np.unique(idx).size == 2

    def test_refill_validation(self):
        base = make_sampler("uniform", N, K)
        with pytest.raises(ValueError, match="refill"):
            AdmissionSampler(base, refill=N + 1)
        with pytest.raises(ValueError, match="refill"):
            AdmissionSampler(base, refill=0)


# ------------------------------------------------------------ merge math
class TestMergeAsync:
    def test_discount_fresh_is_one(self):
        d = staleness_discount(jnp.asarray([0.0, 1.0, 3.0]), lam=0.5)
        np.testing.assert_allclose(np.asarray(d),
                                   [(1.0) ** -0.5, 2.0 ** -0.5, 4.0 ** -0.5],
                                   rtol=1e-6)

    def test_matches_manual(self):
        rng = np.random.RandomState(0)
        cur = [jnp.asarray(rng.randn(4, 3), jnp.float32)]
        dl = jnp.asarray(rng.randn(2, 4, 3), jnp.float32)
        w = jnp.asarray([0.4, 0.6], jnp.float32)
        tau = jnp.asarray([0.0, 2.0], jnp.float32)
        out = merge_async(cur, [dl], w, tau, lam=1.0)
        lam_w = np.asarray([0.4 * 1.0, 0.6 / 3.0], np.float32)
        want = np.asarray(cur[0]) + np.tensordot(
            lam_w, np.asarray(dl), axes=1)
        np.testing.assert_allclose(np.asarray(out[0]), want, rtol=1e-6)

    def test_zero_staleness_full_weight(self):
        cur = [jnp.zeros((2, 2), jnp.float32)]
        dl = jnp.ones((1, 2, 2), jnp.float32)
        out = merge_async(cur, [dl], jnp.asarray([1.0]),
                          jnp.asarray([0.0]), lam=0.7)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.ones((2, 2), np.float32))


# ------------------------------------------------------- LM launcher
class TestLMAsyncLauncher:
    """``launch.train --async`` in LM mode: generations dispatch against
    the live params and ``apply_merge`` scatters each completed client's
    rows back into the client bank."""

    FLAGS = ["--arch", "granite-8b", "--preset", "smoke", "--layers", "3",
             "--scheme", "sfl_ga", "--optimizer", "sgd", "--clients", "4",
             "--cohort", "2", "--buffer", "1", "--batch", "1", "--seq", "16",
             "--steps", "3", "--seed", "0", "--async", "--quiet"]

    def _run(self, tmp_path, name, monkeypatch):
        from repro.launch import train as train_mod

        dispatched = set()
        gen = train_mod._LMAsyncExecutor.run_generation
        sync = train_mod._LMAsyncExecutor.run_sync

        def spy_gen(self, d, idx, w):
            dispatched.update(int(i) for i in np.asarray(idx))
            return gen(self, d, idx, w)

        def spy_sync(self, d, idx, w):
            dispatched.update(int(i) for i in np.asarray(idx))
            return sync(self, d, idx, w)

        monkeypatch.setattr(train_mod._LMAsyncExecutor, "run_generation",
                            spy_gen)
        monkeypatch.setattr(train_mod._LMAsyncExecutor, "run_sync",
                            spy_sync)
        ck = str(tmp_path / f"{name}.ckpt")
        out = train_mod.main(self.FLAGS + ["--checkpoint", ck])
        return out, ck, dispatched

    def test_merges_are_finite_deterministic_and_local(self, tmp_path,
                                                       monkeypatch):
        from repro.checkpoint import load_checkpoint
        from repro.configs import get_config, reduced_config
        from repro.core import algorithms as alg
        from repro.models import lm
        from repro.optim import make_optimizer

        a, ck_a, seen = self._run(tmp_path, "a", monkeypatch)
        b, ck_b, _ = self._run(tmp_path, "b", monkeypatch)
        assert a["async"] == b["async"] and a["async"]["merges"] >= 3
        for k in ("first_loss", "final_loss"):
            assert np.isfinite(a[k]) and a[k] == b[k]
        with open(ck_a, "rb") as fa, open(ck_b, "rb") as fb:
            assert fa.read() == fb.read(), "two --async runs diverged"
        # a client never dispatched keeps its initial rows; one that was
        # dispatched and merged has moved
        cfg = reduced_config(get_config("granite-8b")).with_overrides(
            num_layers=3)
        init = alg.split_lm_params(
            lm.init_lm(jax.random.key(0), lm.build_plan(cfg, 1),
                       jnp.float32), 4)
        like = {"params": init,
                "opt": make_optimizer("sgd", 1e-3).init(init)}
        state, _ = load_checkpoint(ck_a, like)
        never = sorted(set(range(4)) - seen)
        assert never, f"every client was dispatched ({sorted(seen)})"
        got = jax.tree.leaves(state["params"]["client"])
        want = jax.tree.leaves(init["client"])
        for x, y in zip(got, want):
            np.testing.assert_array_equal(np.asarray(x)[never],
                                          np.asarray(y)[never])
        assert any(not np.array_equal(np.asarray(x)[sorted(seen)],
                                      np.asarray(y)[sorted(seen)])
                   for x, y in zip(got, want))
