"""Cohort engine (DESIGN.md §13): partial participation + O(1) server state.

Pins the tentpole contracts:

* samplers are pure in (seed, t) — checkpoint/resume replays the same
  cohort schedule bit-identically;
* the K=N identity cohort (and uniform sampling at K=N, which sorts to
  the identity) reproduces full-participation rounds bit for bit;
* sampled-ρ aggregation is UNBIASED: the expectation of the anchored
  Horvitz-Thompson aggregate over many cohorts matches full
  participation;
* the server model is stored as ONE copy (no leading N axis) and
  non-participant bank entries are untouched by a round;
* traffic / migration are priced for the K participants;
* the CCC envs observe and allocate for K participants;
* the LLM gather/scatter helpers round-trip the bank.
"""
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs.paper_cnn import LIGHT_CONFIG  # noqa: E402
from repro.core.cohort import (SAMPLERS, CohortSampler,  # noqa: E402
                               make_sampler)
from repro.core.protocol import aggregate_cohort, rho_cohort  # noqa: E402
from repro.core.simulator import FedSimulator, SimConfig  # noqa: E402

N, K, BATCH = 6, 3, 8


def _rho(n, seed=0):
    r = np.random.RandomState(seed).rand(n).astype(np.float64) + 0.5
    return (r / r.sum()).astype(np.float32)


def _data(k, tau=1, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.rand(k, tau, BATCH, 28, 28, 1).astype(np.float32),
            rng.randint(0, 10, (k, tau, BATCH)))


def _sim(scheme="sfl_ga", cut=2, cohort=None, sampler="full", n=N,
         rho=None, seed=0, **kw):
    return FedSimulator(
        LIGHT_CONFIG,
        SimConfig(scheme=scheme, cut=cut, n_clients=n, batch=BATCH,
                  cohort=cohort, sampler=sampler, **kw),
        rho=rho, seed=seed)


# ---------------------------------------------------------------- samplers
class TestSampler:
    def test_shapes_and_ranges(self):
        rho = _rho(N)
        for kind in SAMPLERS:
            k = N if kind == "full" else K
            s = make_sampler(kind, N, k, rho=rho, seed=3)
            idx, w = s.cohort(5)
            assert idx.shape == (k,) and w.shape == (k,)
            assert w.dtype == np.float32
            assert np.all((0 <= idx) & (idx < N))
            if kind != "rho":  # without replacement: distinct
                assert len(set(idx.tolist())) == k

    def test_pure_in_t(self):
        for kind in ("uniform", "rho", "latency"):
            a = make_sampler(kind, N, K, rho=_rho(N), seed=7)
            b = make_sampler(kind, N, K, rho=_rho(N), seed=7)
            for t in (0, 3, 17):
                ia, wa = a.cohort(t)
                ib, wb = b.cohort(t)
                np.testing.assert_array_equal(ia, ib)
                np.testing.assert_array_equal(wa, wb)
        s = make_sampler("uniform", 100, 10, seed=7)
        assert not np.array_equal(s.cohort(0)[0], s.cohort(1)[0])

    def test_uniform_at_k_equals_n_is_identity(self):
        rho = _rho(N)
        s = make_sampler("uniform", N, N, rho=rho, seed=11)
        idx, w = s.cohort(4)
        np.testing.assert_array_equal(idx, np.arange(N))
        np.testing.assert_array_equal(w, rho)  # π=1 ⇒ exact ρ
        assert not s.anchored

    def test_full_requires_k_n(self):
        with pytest.raises(ValueError, match="full"):
            make_sampler("full", N, K)
        with pytest.raises(ValueError, match="unknown sampler"):
            make_sampler("nope", N, K)
        with pytest.raises(ValueError, match="cohort size"):
            make_sampler("uniform", N, N + 1)

    def test_rho_sampler_weights(self):
        s = make_sampler("rho", N, K, rho=_rho(N), seed=0)
        _, w = s.cohort(0)
        np.testing.assert_allclose(w, 1.0 / K)
        assert s.anchored

    def test_latency_picks_fastest(self):
        lat = np.asarray([5.0, 1.0, 9.0, 0.5, 7.0, 2.0])
        s = make_sampler("latency", N, K, rho=_rho(N), seed=0,
                         latency_fn=lambda t: lat)
        idx, w = s.cohort(2)
        np.testing.assert_array_equal(idx, [1, 3, 5])  # 3 smallest, sorted
        assert w.sum() == pytest.approx(1.0, rel=1e-6)

    def test_default_latency_fn_runs(self):
        s = make_sampler("latency", N, K, seed=1)
        i0, _ = s.cohort(0)
        i1, _ = s.cohort(1)
        assert i0.shape == (K,)  # block fading varies the pick over rounds
        assert all(s.cohort(0)[0].tolist() == i0.tolist() for _ in range(2))

    def test_peek_is_pure_lookahead(self):
        """peek(t) == cohort(t), and peeking — any number of times, in
        any order — perturbs no later cohort (the bank prefetcher's
        correctness precondition)."""
        for kind in ("uniform", "rho", "latency"):
            s = make_sampler(kind, N, K, rho=_rho(N), seed=7)
            pi, pw = s.peek(5)
            s.peek(0)
            s.peek(9)  # interleaved peeks consume no schedule state
            ci, cw = s.cohort(5)
            np.testing.assert_array_equal(pi, ci)
            np.testing.assert_array_equal(pw, cw)
            ref = make_sampler(kind, N, K, rho=_rho(N), seed=7)
            np.testing.assert_array_equal(s.cohort(6)[0], ref.cohort(6)[0])

    def test_rho_cohort_ht_weights(self):
        rho = _rho(8)
        idx = np.asarray([1, 4, 6])
        w = rho_cohort(rho, idx, 3 / 8)
        np.testing.assert_allclose(w, rho[idx] * (8 / 3), rtol=1e-6)


# ------------------------------------------------------------- unbiasedness
class TestUnbiasedAggregation:
    def _estimate(self, kind, n_draws=4000, seed=0):
        n, k = 8, 3
        rho = _rho(n, seed=2)
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(n, 4).astype(np.float32))
        anchor = jnp.asarray(rng.randn(4).astype(np.float32))
        s = make_sampler(kind, n, k, rho=rho, seed=seed)
        acc = np.zeros(4, np.float64)
        for t in range(n_draws):
            idx, w = s.cohort(t)
            est = aggregate_cohort(x[jnp.asarray(idx)], jnp.asarray(w),
                                   anchor=anchor)
            acc += np.asarray(est, np.float64)
        full = np.asarray(anchor) + np.einsum(
            "n,nd->d", rho.astype(np.float64),
            np.asarray(x, np.float64) - np.asarray(anchor, np.float64))
        return acc / n_draws, full

    @pytest.mark.parametrize("kind", ["uniform", "rho"])
    def test_expectation_matches_full_participation(self, kind):
        est, full = self._estimate(kind)
        np.testing.assert_allclose(est, full, atol=0.05)

    def test_plain_aggregate_matches_param_average_rows(self):
        from repro.core.gradagg import client_param_average

        rho = jnp.asarray(_rho(5))
        tree = {"w": jnp.asarray(np.random.RandomState(0)
                                 .randn(5, 3, 2).astype(np.float32))}
        single = aggregate_cohort(tree, rho)
        rows = client_param_average(tree, rho)
        np.testing.assert_array_equal(np.asarray(single["w"]),
                                      np.asarray(rows["w"][0]))


# --------------------------------------------------------- identity parity
class TestIdentityParity:
    @pytest.mark.parametrize("scheme", ["sfl_ga", "sfl", "psl", "fl"])
    def test_uniform_kn_bitidentical_to_full(self, scheme):
        """K=N uniform sampling sorts to the identity permutation with
        exact ρ weights — bit-identical rounds to full participation."""
        rho = _rho(N, seed=4)
        cut = 1 if scheme != "fl" else 1
        a = _sim(scheme, cut=cut, rho=rho)
        b = _sim(scheme, cut=cut, cohort=N, sampler="uniform", rho=rho)
        for r in range(3):
            x, y = _data(N, seed=r)
            ma = a.run_round(x, y)
            mb = b.run_round(x, y)
            assert ma == mb
        for pa, pb in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
            np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


# ------------------------------------------------------- partial mechanics
class TestPartialParticipation:
    def test_server_is_one_copy(self):
        sim = _sim(cohort=K, sampler="uniform")
        from repro.models import cnn

        ref = cnn.init_cnn(jax.random.key(0), LIGHT_CONFIG)
        for got, want in zip(jax.tree.leaves(sim.state["server"]),
                             jax.tree.leaves(ref[2:])):
            assert got.shape == want.shape  # no leading N axis
        sim.run_round(*_data(K))
        for got, want in zip(jax.tree.leaves(sim.state["server"]),
                             jax.tree.leaves(ref[2:])):
            assert got.shape == want.shape

    def test_nonparticipants_untouched(self):
        sim = _sim(cohort=K, sampler="uniform")
        before = jax.tree.map(np.asarray, sim.state["client"])
        idx, _ = sim.cohort_for_round(0)
        sim.run_round(*_data(K))
        out = set(range(N)) - set(idx.tolist())
        assert out  # K < N: someone sat out
        for a, b in zip(jax.tree.leaves(before),
                        jax.tree.leaves(sim.state["client"])):
            for i in out:
                np.testing.assert_array_equal(a[i], np.asarray(b)[i])
            changed = any(not np.array_equal(a[i], np.asarray(b)[i])
                          for i in idx.tolist())
            assert changed or a.ndim == 0

    @pytest.mark.parametrize("scheme", ["sfl_ga", "sfl", "psl", "fl"])
    @pytest.mark.parametrize("sampler", ["uniform", "rho", "latency"])
    def test_all_schemes_and_samplers_run(self, scheme, sampler):
        cut = 2 if scheme != "fl" else 1
        sim = _sim(scheme, cut=cut, cohort=K, sampler=sampler)
        for r in range(2):
            m = sim.run_round(*_data(K, seed=r))
            assert np.isfinite(m["loss"])
        if scheme in ("sfl", "fl"):
            assert m["client_drift"] == 0.0  # collapsed bank

    def test_wrong_cohort_data_shape_rejected(self):
        sim = _sim(cohort=K, sampler="uniform")
        with pytest.raises(ValueError, match="participants"):
            sim.run_round(*_data(N))

    def test_traffic_priced_for_participants(self):
        from repro.sysmodel.traffic import round_traffic_bits
        from repro.models import cnn

        sim = _sim(cohort=K, sampler="uniform", cut=2)
        want = round_traffic_bits(
            "sfl_ga", n_clients=K, tau=1,
            smashed_elems=cnn.smashed_numel(LIGHT_CONFIG, 2) * BATCH,
            label_bits=BATCH * 32,
            client_model_bits=cnn.phi(LIGHT_CONFIG, 2) * 32,
            full_model_bits=cnn.total_params(LIGHT_CONFIG) * 32)
        assert sim.comm_bits_per_round() == want

    def test_migration_priced_for_participants(self):
        from repro.models import cnn

        sim = _sim(cohort=K, sampler="uniform", cut=2)
        bits = sim.set_cut(3)
        delta = cnn.phi(LIGHT_CONFIG, 3) - cnn.phi(LIGHT_CONFIG, 2)
        assert bits["down_bits"] == delta * 32 * K  # ×K, not ×N

    def test_tau_cohort_batches(self):
        sim = _sim(cohort=K, sampler="uniform", tau=2)
        m = sim.run_round(*_data(K, tau=2))
        assert np.isfinite(m["loss"])


# ------------------------------------------------------------------ resume
class TestCohortResume:
    def _run(self, sim, parts, train, rounds, rng):
        from repro.data.federated import round_batches

        for _ in range(rounds):
            idx, _ = sim.cohort_for_round(sim._t)
            xs, ys = round_batches(train, parts, BATCH, 1, rng, idx=idx)
            sim.run_round(xs, ys)

    def test_schedule_and_state_survive_resume(self, tmp_path):
        from repro.data import iid_partition, make_image_dataset
        from repro.data.federated import rho_weights, round_batches

        ds = make_image_dataset("mnist", n=600, seed=0)
        parts = iid_partition(len(ds.x), N, seed=0)
        rho = rho_weights(parts)
        kw = dict(cohort=K, sampler="uniform", rho=rho, cohort_seed=5)
        path = str(tmp_path / "cohort.ckpt")

        ref = _sim(**kw)
        self._run(ref, parts, ds, 4, np.random.RandomState(9))

        half = _sim(**kw)
        rng = np.random.RandomState(9)
        self._run(half, parts, ds, 2, rng)
        half.save(path)

        resumed = _sim(**kw)
        resumed.restore(path)
        assert resumed._t == 2
        # the NEXT cohorts equal the uninterrupted run's rounds 2..3
        for t in (2, 3):
            ia, _ = ref.cohort_for_round(t)
            ib, _ = resumed.cohort_for_round(t)
            np.testing.assert_array_equal(ia, ib)
        rng2 = np.random.RandomState(9)
        for t in range(2):  # fast-forward the data stream
            idx, _ = resumed.cohort_for_round(t)
            round_batches(ds, parts, BATCH, 1, rng2, idx=idx)
        self._run(resumed, parts, ds, 2, rng2)
        for a, b in zip(jax.tree.leaves(ref.state),
                        jax.tree.leaves(resumed.state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_peek_matches_cohort_across_restore(self, tmp_path):
        """peek(t+1) then cohort_for_round(t+1) agree — including when a
        checkpoint/restore sits between the peek and the round."""
        kw = dict(cohort=K, sampler="uniform", cohort_seed=5)
        path = str(tmp_path / "peek.ckpt")
        sim = _sim(**kw)
        peeked, _ = sim.sampler.peek(3)
        sim.save(path)
        resumed = _sim(**kw)
        resumed.restore(path)
        np.testing.assert_array_equal(peeked, resumed.sampler.peek(3)[0])
        np.testing.assert_array_equal(peeked, resumed.cohort_for_round(3)[0])

    def test_restore_rejects_cohort_mismatch(self, tmp_path):
        path = str(tmp_path / "c.ckpt")
        _sim(cohort=K, sampler="uniform").save(path)
        other = _sim(cohort=2, sampler="uniform")
        with pytest.raises(ValueError, match="cohort"):
            other.restore(path)
        other2 = _sim(cohort=K, sampler="rho")
        with pytest.raises(ValueError, match="sampler"):
            other2.restore(path)


# ------------------------------------------------------------------- envs
class TestEnvCohort:
    def _cfg(self, **kw):
        from repro.ccc.env import cnn_env_config

        return cnn_env_config(n_clients=N, batch=BATCH, horizon=4, seed=0,
                              **kw)

    def test_scalar_env_observes_k(self):
        from repro.ccc.env import CuttingPointEnv

        env = CuttingPointEnv(self._cfg(cohort=K))
        assert env.state_dim == K + 1
        obs = env.reset()
        assert obs.shape == (K + 1,)
        assert env.gains.shape == (K,)
        _, r, _, info = env.step(0)
        assert np.isfinite(r)
        assert np.isfinite(info["chi"])  # P2.1 solved over K gains

    def test_scalar_env_set_cohort(self):
        from repro.ccc.env import CuttingPointEnv

        env = CuttingPointEnv(self._cfg(cohort=K))
        idx = np.asarray([0, 2, 4])
        env.set_cohort(idx)
        env.reset()
        # gains now derive from exactly those clients' distances
        ray = env.gains / (10 ** (-(128.1 + 37.6 * np.log10(
            np.maximum(env._dists[idx], 1e-3))) / 10))
        assert np.all(ray > 0)
        with pytest.raises(ValueError, match="cohort index shape"):
            env.set_cohort(np.asarray([0, 1]))
        env.set_cohort(None)  # revert to internal sampling
        env.reset()
        assert env.gains.shape == (K,)

    def test_default_env_unchanged(self):
        """cohort=None keeps the paper's N-client env bit-identical
        (same rng consumption, same state_dim)."""
        from repro.ccc.env import CuttingPointEnv

        a = CuttingPointEnv(self._cfg())
        b = CuttingPointEnv(self._cfg(cohort=None))
        np.testing.assert_array_equal(a.reset(), b.reset())
        assert a.state_dim == N + 1

    def test_batched_env_cohort(self):
        from repro.ccc.env import BatchedCuttingPointEnv

        env = BatchedCuttingPointEnv(self._cfg(cohort=K), n_envs=4)
        assert env.state_dim == K + 1
        state, obs = env.reset(jax.random.key(0))
        assert obs.shape == (4, K + 1)
        state2, obs2, r, done, info = env.step(
            state, jnp.zeros(4, jnp.int32))
        assert obs2.shape == (4, K + 1)
        assert bool(jnp.all(jnp.isfinite(r)))

    def test_closed_loop_threads_cohort(self):
        from repro.ccc.env import CuttingPointEnv
        from repro.core.closed_loop import CutSchedule, run_closed_loop
        from repro.data import iid_partition, make_image_dataset
        from repro.data.federated import rho_weights

        ds = make_image_dataset("mnist", n=400, seed=0)
        train, test = ds.split(0.9)
        parts = iid_partition(len(train.x), N, seed=0)
        sim = _sim(cohort=K, sampler="uniform", rho=rho_weights(parts))
        env = CuttingPointEnv(self._cfg(cohort=K))
        res = run_closed_loop(sim, env, CutSchedule.from_sequence([2, 3]),
                              train, test, parts, rounds=3, eval_every=3,
                              batch_seed=0)
        assert len(res.cuts) == 3 and res.n_migrations >= 1
        assert np.isfinite(res.total_latency_s)

    def test_closed_loop_rejects_mismatched_cohort(self):
        from repro.ccc.env import CuttingPointEnv
        from repro.core.closed_loop import CutSchedule, run_closed_loop

        sim = _sim(cohort=K, sampler="uniform")
        env = CuttingPointEnv(self._cfg())  # N participants, not K
        with pytest.raises(AssertionError, match="participants"):
            run_closed_loop(sim, env, CutSchedule.constant(2), None, None,
                            [], rounds=1)


# ------------------------------------------------------------ data surfacing
class TestDataLossSurfacing:
    def test_iid_sizes_leftover_warns(self):
        from repro.data.federated import iid_partition

        with pytest.warns(UserWarning, match="dropping 40 samples"):
            iid_partition(100, 3, sizes=[20, 20, 20])
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            iid_partition(60, 3, sizes=[20, 20, 20])  # exact: silent

    def test_more_clients_than_samples_warns(self):
        from repro.data.federated import iid_partition

        with pytest.warns(UserWarning, match="EMPTY"):
            iid_partition(5, 8)

    def test_replacement_warns_and_stat(self):
        from repro.data.federated import (client_batches,
                                          replacement_fraction)
        from repro.data.synthetic import make_image_dataset

        ds = make_image_dataset("mnist", n=40, seed=0)
        parts = [np.arange(4), np.arange(4, 40)]
        assert replacement_fraction(parts, 8) == 0.5
        assert replacement_fraction(parts, 8, idx=[1]) == 0.0
        with pytest.warns(UserWarning, match="WITH replacement"):
            client_batches(ds, parts, 8, np.random.RandomState(0))

    def test_empty_partition_raises(self):
        from repro.data.federated import client_batches
        from repro.data.synthetic import make_image_dataset

        ds = make_image_dataset("mnist", n=10, seed=0)
        with pytest.raises(ValueError, match="empty client partition"):
            client_batches(ds, [np.arange(5), np.asarray([], np.int64)],
                           4, np.random.RandomState(0))

    def test_round_batches_idx_matches_subset(self):
        from repro.data.federated import round_batches
        from repro.data.synthetic import make_image_dataset

        ds = make_image_dataset("mnist", n=100, seed=0)
        parts = [np.arange(i * 20, (i + 1) * 20) for i in range(5)]
        xa, ya = round_batches(ds, parts, 4, 2, np.random.RandomState(3),
                               idx=[1, 4])
        assert xa.shape[:3] == (2, 2, 4)
        # identity idx reproduces the no-idx stream draw for draw
        xb, _ = round_batches(ds, parts, 4, 1, np.random.RandomState(3))
        xc, _ = round_batches(ds, parts, 4, 1, np.random.RandomState(3),
                              idx=range(5))
        np.testing.assert_array_equal(xb, xc)


# -------------------------------------------------------------- eval jit
class TestEvaluateJit:
    def test_matches_eager_reference(self):
        from repro.models import cnn

        sim = _sim()
        sim.run_round(*_data(N))
        rng = np.random.RandomState(1)
        x = rng.rand(700, 28, 28, 1).astype(np.float32)
        y = rng.randint(0, 10, 700)
        acc = sim.evaluate(x, y, batch=256)  # 2 shapes: 256 + 188 tail
        params = sim.global_params()
        logits = cnn.forward_blocks(params, jnp.asarray(x), LIGHT_CONFIG,
                                    0, LIGHT_CONFIG.num_layers)
        ref = float(np.mean(np.asarray(jnp.argmax(logits, -1)) == y))
        assert acc == pytest.approx(ref, abs=1e-9)


# ------------------------------------------------------------------ LLM
class TestLMCohort:
    def _setup(self, algo="sfl_ga", n=3):
        from repro.configs import TrainConfig, get_config, reduced_config
        from repro.core import algorithms as alg
        from repro.models import lm
        from repro.optim import make_optimizer

        cfg = reduced_config(get_config("granite-8b")).with_overrides(
            num_layers=2, d_model=64, d_ff=128, vocab_size=256,
            num_heads=2, num_kv_heads=1, head_dim=32)
        plan = lm.build_plan(cfg, 1)
        params = alg.split_lm_params(
            lm.init_lm(jax.random.key(0), plan, jnp.float32), n)
        tcfg = TrainConfig(model=cfg, algo=algo, cut_layer=1,
                           compute_dtype="float32", remat=False)
        opt = make_optimizer("adamw", 1e-3)
        return cfg, plan, tcfg, opt, params

    def test_gather_scatter_roundtrip(self):
        from repro.core import algorithms as alg

        _, _, _, opt, params = self._setup()
        opt_state = opt.init(params)
        idx = np.asarray([0, 2])
        c = alg.gather_cohort(params, idx)
        co = alg.gather_cohort_opt(opt_state, idx)
        assert jax.tree.leaves(c["client"])[0].shape[0] == 2
        back = alg.scatter_cohort(params, c, idx)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        back_opt = alg.scatter_cohort_opt(opt_state, co, idx)
        for a, b in zip(jax.tree.leaves(opt_state),
                        jax.tree.leaves(back_opt)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @staticmethod
    def _eager_gather(tree, idx):
        jidx = jnp.asarray(idx)
        return dict(tree, client=jax.tree.map(lambda x: x[jidx],
                                              tree["client"]))

    @staticmethod
    def _eager_scatter(bank, cohort, idx, broadcast=False):
        jidx = jnp.asarray(idx)
        if broadcast:
            client = jax.tree.map(
                lambda b, u: jnp.broadcast_to(
                    u[0][None], b.shape).astype(b.dtype),
                bank["client"], cohort["client"])
        else:
            client = jax.tree.map(lambda b, u: b.at[jidx].set(u),
                                  bank["client"], cohort["client"])
        return dict(bank, client=client, server=cohort["server"])

    @pytest.mark.parametrize("move", ["gather", "scatter", "gather_opt",
                                      "scatter_opt", "broadcast"])
    def test_cohort_moves_match_eager_expressions(self, move):
        """Each compiled gather/scatter gives the per-leaf eager
        expressions' trees bit for bit, on an unsorted cohort."""
        from repro.core import algorithms as alg

        _, _, _, opt, params = self._setup(n=5)
        idx = np.asarray([3, 0, 4])

        def distinct(tree):  # distinct rows, so a misplaced row shows
            return jax.tree.map(
                lambda x: x + jnp.arange(x.size, dtype=x.dtype).reshape(
                    x.shape) * 1e-3, tree)

        opt_state = opt.init(params)
        opt_state = dict(opt_state, m=distinct(opt_state["m"]),
                         v=distinct(opt_state["v"]))
        params = distinct(params)

        def bump(tree):
            return jax.tree.map(lambda x: x * 2.0 + 1.0, tree)

        if move == "gather":
            got = alg.gather_cohort(params, idx)
            want = self._eager_gather(params, idx)
        elif move == "gather_opt":
            got = alg.gather_cohort_opt(opt_state, idx)
            want = dict(opt_state, **{k: self._eager_gather(opt_state[k], idx)
                                      for k in ("m", "v")})
        elif move == "scatter_opt":
            cohort = dict(opt_state, **{
                k: bump(self._eager_gather(opt_state[k], idx))
                for k in ("m", "v")})
            got = alg.scatter_cohort_opt(opt_state, cohort, idx)
            want = dict(cohort, **{
                k: self._eager_scatter(opt_state[k], cohort[k], idx)
                for k in ("m", "v")})
        else:
            broadcast = move == "broadcast"
            cohort = bump(self._eager_gather(params, idx))
            got = alg.scatter_cohort(params, cohort, idx,
                                     broadcast_client=broadcast)
            want = self._eager_scatter(params, cohort, idx, broadcast)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if move in ("gather", "scatter", "broadcast"):
            # the shared server side passes through untouched
            assert got["server"] is (params if move == "gather"
                                     else cohort)["server"]

    @pytest.mark.parametrize("helper", ["gather_rows", "scatter_rows",
                                        "broadcast_row0"])
    def test_cohort_programs_compile_once_per_k(self, helper):
        """The cohort index is traced: cohorts of one size K share one
        program, and another K adds exactly one."""
        from repro.core import algorithms as alg
        from repro.core import bank as bank_mod

        _, _, _, _, params = self._setup(n=5)
        fn = getattr(bank_mod, helper)
        fn.clear_cache()

        def move(idx):
            cohort = alg.gather_cohort(params, idx)
            if helper == "gather_rows":
                return
            alg.scatter_cohort(params, cohort, idx,
                               broadcast_client=helper == "broadcast_row0")

        for idx in ([0, 1, 2], [4, 2, 3], [3, 0, 1]):
            move(np.asarray(idx))
        assert fn._cache_size() == 1
        move(np.asarray([1, 4]))
        assert fn._cache_size() == 2

    @pytest.mark.parametrize("program", ["gather_rows", "scatter_rows",
                                         "broadcast_row0"])
    def test_bank_and_cohort_helpers_share_one_program(self, program):
        """A ``device`` ClientBank and ``gather_cohort``/``scatter_cohort``
        on the same client tree run one compiled program between them —
        one launch for the whole L-leaf tree, not one per leaf — and
        give the same rows."""
        from repro.core import algorithms as alg
        from repro.core import bank as bank_mod

        _, _, _, _, params = self._setup(n=5)
        assert len(jax.tree.leaves(params["client"])) > 1
        fn = getattr(bank_mod, program)
        fn.clear_cache()
        bank = bank_mod.ClientBank(params["client"], n_clients=5,
                                   stacked=True, backend="device")
        idx = np.asarray([3, 0, 4])
        broadcast = program == "broadcast_row0"
        got = bank.gather(idx)
        if program != "gather_rows":
            upd = jax.tree.map(lambda x: x * 2.0 + 1.0, got)
            bank.scatter(idx, upd, broadcast=broadcast)
            got = bank.tree
        assert fn._cache_size() == 1  # the bank compiled it
        cohort = alg.gather_cohort(params, idx)
        want = cohort["client"]
        if program != "gather_rows":
            bumped = dict(cohort, client=jax.tree.map(
                lambda x: x * 2.0 + 1.0, want))
            want = alg.scatter_cohort(params, bumped, idx,
                                      broadcast_client=broadcast)["client"]
        assert fn._cache_size() == 1  # ... and the helpers reused it
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_partial_step_leaves_nonparticipants(self):
        from repro.core import algorithms as alg

        cfg, plan, tcfg, opt, params = self._setup(n=3)
        step = jax.jit(alg.make_train_step(plan, tcfg, opt, 2))
        opt_state = opt.init(params)
        rng = np.random.RandomState(0)
        idx = np.asarray([0, 2])
        w = jnp.asarray([0.5, 0.5])
        batch = {"tokens": jnp.asarray(rng.randint(0, 256, (2, 2, 16))),
                 "labels": jnp.asarray(rng.randint(0, 256, (2, 2, 16))),
                 "rho": w}
        cp, cop, m = step(alg.gather_cohort(params, idx),
                          alg.gather_cohort_opt(opt_state, idx), batch)
        assert np.isfinite(float(m["loss"]))
        new = alg.scatter_cohort(params, cp, idx)
        for a, b in zip(jax.tree.leaves(params["client"]),
                        jax.tree.leaves(new["client"])):
            np.testing.assert_array_equal(np.asarray(a)[1],
                                          np.asarray(b)[1])  # sat out
            assert not np.array_equal(np.asarray(a)[0], np.asarray(b)[0])

    def test_sfl_broadcast_aggregate(self):
        from repro.core import algorithms as alg

        cfg, plan, tcfg, opt, params = self._setup(algo="sfl", n=3)
        step = jax.jit(alg.make_train_step(plan, tcfg, opt, 2))
        opt_state = opt.init(params)
        rng = np.random.RandomState(1)
        idx = np.asarray([1, 2])
        batch = {"tokens": jnp.asarray(rng.randint(0, 256, (2, 2, 16))),
                 "labels": jnp.asarray(rng.randint(0, 256, (2, 2, 16))),
                 "rho": jnp.asarray([0.5, 0.5])}
        cp, cop, _ = step(alg.gather_cohort(params, idx),
                          alg.gather_cohort_opt(opt_state, idx), batch)
        new = alg.scatter_cohort(params, cp, idx, broadcast_client=True)
        for leaf in jax.tree.leaves(new["client"]):
            a = np.asarray(leaf)
            for i in range(1, a.shape[0]):
                np.testing.assert_array_equal(a[0], a[i])  # global model
