"""Compression subsystem tests: codec round-trip properties, the
compressed gradagg operator, the fused Pallas kernels vs their oracles,
error feedback, and the codec-aware bit accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.compress import IntQuantCodec, PassthroughCodec, get_codec
from repro.core.gradagg import (gradagg, make_gradagg_compressed,
                                uniform_rho)
from repro.kernels import ops, ref
from repro.sysmodel.payload import compression_ratio, payload_bits, spec_for

KEY = jax.random.key(0)


# ---------------------------------------------------------------- codecs
class TestCodecRoundTrip:
    @settings(max_examples=15, deadline=None)
    @given(bits=st.sampled_from([4, 8]), n=st.integers(1, 900),
           seed=st.integers(0, 999))
    def test_int_quant_error_bounded_by_scale(self, bits, n, seed):
        """|x - decode(encode(x))| < scale of the element's tile, for any
        shape (padding path included) and any stochastic-rounding seed."""
        x = jax.random.normal(jax.random.key(seed), (n,), jnp.float32) * 3.0
        codec = get_codec(f"int{bits}")
        p = codec.encode(x, seed)
        xh = codec.decode(p)
        scale_full = jnp.repeat(p.scale, codec.tile)[:n]
        err = jnp.abs(xh - x)
        assert bool(jnp.all(err <= scale_full + 1e-7)), float(err.max())

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 999))
    def test_stochastic_rounding_unbiased(self, seed):
        """E[decode(encode(x))] ≈ x across independent seeds."""
        x = jax.random.normal(jax.random.key(seed), (512,), jnp.float32)
        codec = get_codec("int8")
        acc = jnp.zeros_like(x)
        reps = 64
        for r in range(reps):
            acc = acc + codec.roundtrip(x, seed * 1000 + r)
        mean_err = float(jnp.max(jnp.abs(acc / reps - x)))
        scale = float(jnp.max(jnp.abs(x))) / 127
        assert mean_err < scale, (mean_err, scale)  # << scale = unbiased

    def test_passthrough_is_identity_object(self):
        x = jax.random.normal(KEY, (4, 7), jnp.float32)
        c = PassthroughCodec()
        assert c.roundtrip(x) is x  # not just equal: the same array

    def test_cast_codecs_match_astype(self):
        x = jax.random.normal(KEY, (64,), jnp.float32)
        for name, dt in (("bf16", jnp.bfloat16),
                         ("fp8", getattr(jnp, "float8_e4m3fn", None))):
            if dt is None:
                continue
            np.testing.assert_array_equal(
                np.asarray(get_codec(name).roundtrip(x)),
                np.asarray(x.astype(dt).astype(jnp.float32)))

    def test_topk_rejects_unpriceable_density(self):
        from repro.compress import TopKCodec

        for bad in (0.125, 0.004, 0.995):
            with pytest.raises(ValueError):
                TopKCodec(bad)
        assert TopKCodec(0.25).payload_bits((100,)) > 0

    def test_topk_keeps_largest(self):
        x = jnp.asarray(np.random.RandomState(0).randn(200), jnp.float32)
        c = get_codec("topk10")
        xh = c.decode(c.encode(x))
        kept = np.nonzero(np.asarray(xh))[0]
        assert len(kept) == 20
        thresh = np.sort(np.abs(np.asarray(x)))[-20]
        assert np.all(np.abs(np.asarray(x))[kept] >= thresh)

    def test_codecs_jit_and_vmap(self):
        """Simulator wiring vmaps roundtrip over clients under jit."""
        x = jax.random.normal(KEY, (3, 8, 16), jnp.float32)
        seeds = jnp.arange(3, dtype=jnp.uint32)
        for name in ("int8", "int4", "bf16", "topk25"):
            c = get_codec(name)
            out = jax.jit(jax.vmap(c.roundtrip))(x, seeds)
            assert out.shape == x.shape and bool(jnp.all(jnp.isfinite(out)))


class TestErrorFeedback:
    def test_residual_carried_exactly(self):
        c = get_codec("topk10")
        shape = (300,)
        state = c.init_state(shape)
        tot_in = jnp.zeros(shape)
        tot_out = jnp.zeros(shape)
        for r in range(20):
            x = jax.random.normal(jax.random.key(r), shape)
            p, state = c.encode_ef(x, state, r)
            tot_in = tot_in + x
            tot_out = tot_out + c.decode(p)
        # EF invariant: carried state == everything not yet transmitted
        np.testing.assert_allclose(np.asarray(tot_in - tot_out),
                                   np.asarray(state), atol=1e-4)

    def test_ef_beats_plain_topk_over_rounds(self):
        """Accumulated EF transmissions approximate the signal better than
        memoryless top-k on a persistent (non-zero-mean) component."""
        c = get_codec("topk10")
        base = jax.random.normal(jax.random.key(42), (400,))
        state = c.init_state(base.shape)
        ef_sum, plain_sum = jnp.zeros_like(base), jnp.zeros_like(base)
        rounds = 15
        for r in range(rounds):
            p, state = c.encode_ef(base, state, r)
            ef_sum = ef_sum + c.decode(p)
            plain_sum = plain_sum + c.decode(c.encode(base, r))
        target = base * rounds
        assert float(jnp.linalg.norm(ef_sum - target)) < \
            float(jnp.linalg.norm(plain_sum - target))

    def test_stateless_codecs_pass_state_through(self):
        c = get_codec("int8")
        x = jnp.ones((8,))
        p, state = c.encode_ef(x, None, 0)
        assert state is None


# ------------------------------------------------------- gradagg operator
class TestGradaggCompressed:
    def test_passthrough_equals_gradagg_bitexact(self):
        x = jax.random.normal(KEY, (4, 8, 32), jnp.float32)
        rho = uniform_rho(4)
        ct = jax.random.normal(jax.random.key(1), x.shape, jnp.float32)
        f_plain = jax.jit(jax.value_and_grad(
            lambda x: jnp.vdot(gradagg(x, rho), ct)))
        f_pass = jax.jit(jax.value_and_grad(
            lambda x: jnp.vdot(make_gradagg_compressed()(x, rho), ct)))
        v1, g1 = f_plain(x)
        v2, g2 = f_pass(x)
        assert float(v1) == float(v2)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))

    @settings(max_examples=8, deadline=None)
    @given(up=st.sampled_from(["fp32", "bf16", "int8", "int4"]),
           down=st.sampled_from(["fp32", "int8"]), seed=st.integers(0, 99))
    def test_bwd_broadcast_and_accuracy(self, up, down, seed):
        """Every client still receives the SAME cotangent, and it stays
        within codec error of the exact ρ-weighted aggregate."""
        n = 5
        rng = np.random.RandomState(seed)
        rho = jnp.asarray(rng.dirichlet([1.0] * n).astype(np.float32))
        ct = jnp.asarray(rng.randn(n, 6, 16).astype(np.float32))
        x = jnp.zeros((n, 6, 16), jnp.float32)
        gfn = make_gradagg_compressed(up, down)
        g = jax.grad(lambda x: jnp.sum(gfn(x, rho, seed) * ct))(x)
        g = np.asarray(g)
        assert np.array_equal(g, np.broadcast_to(g[0:1], g.shape))
        agg = np.einsum("n,nbd->bd", np.asarray(rho), np.asarray(ct))
        tol = {"fp32": 1e-6, "int8": 0.05, "bf16": 0.05,
               "int4": 0.6}[down]
        np.testing.assert_allclose(g[0], agg, atol=tol * np.abs(agg).max()
                                   + 1e-6)

    def test_forward_applies_uplink_codec(self):
        x = jax.random.normal(KEY, (3, 16, 64), jnp.float32)
        rho = uniform_rho(3)
        out = make_gradagg_compressed("int8", "fp32")(x, rho, 1)
        assert not np.array_equal(np.asarray(out), np.asarray(x))
        scale = np.abs(np.asarray(x)).max() / 127
        assert float(jnp.abs(out - x).max()) <= scale + 1e-6

    def test_per_round_seed_varies_rounding(self):
        """A traced per-call seed must change the stochastic draw — the
        operator must not replay one rounding pattern every round."""
        x = jax.random.normal(KEY, (2, 16, 64), jnp.float32)
        rho = uniform_rho(2)
        gfn = jax.jit(make_gradagg_compressed("int8", "fp32"))
        a = gfn(x, rho, jnp.uint32(1))
        b = gfn(x, rho, jnp.uint32(2))
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_channel_helpers_shared_with_simulator(self):
        """gradagg's forward == the simulator's uplink channel, by
        construction (both call repro.compress.uplink_channel)."""
        from repro.compress import get_codec, uplink_channel

        x = jax.random.normal(KEY, (4, 8, 32), jnp.float32)
        rho = uniform_rho(4)
        out = make_gradagg_compressed("int4", "fp32")(x, rho, 9)
        exp = uplink_channel(get_codec("int4"), x, 9)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


# ---------------------------------------------------------- fused kernels
class TestQuantizeKernels:
    @pytest.mark.parametrize("bits,bt,bd", [
        (8, 256, 256), (8, 128, 128), (4, 256, 256), (4, 128, 256),
    ])
    def test_quantize_kernel_bitexact_vs_ref(self, bits, bt, bd):
        g = jax.random.normal(KEY, (3, 256, 512), jnp.float32)
        qk, sk = ops.quantize(g, seed=7, bits=bits, block_t=bt, block_d=bd)
        qr, sr = ops.quantize(g, seed=7, bits=bits, block_t=bt, block_d=bd,
                              backend="jnp")
        np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
        np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))

    @settings(max_examples=8, deadline=None)
    @given(bits=st.sampled_from([4, 8]), n=st.integers(2, 6),
           seed=st.integers(0, 99))
    def test_dequant_agg_kernel_vs_ref(self, bits, n, seed):
        g = jax.random.normal(jax.random.key(seed), (n, 128, 256),
                              jnp.float32)
        rho = jax.nn.softmax(jax.random.normal(jax.random.key(seed + 1),
                                               (n,)))
        q, s = ops.quantize(g, seed=seed, bits=bits)
        out_k = ops.dequant_agg(q, s, rho, bits=bits)
        out_r = ops.dequant_agg(q, s, rho, bits=bits, backend="jnp")
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_fused_path_approximates_exact_aggregate(self, bits):
        # dequant(quantize(g)) against the unquantized eq. 5 reduction:
        # stochastic rounding moves each value by under one step, and the
        # ρ weights sum to 1, so the aggregate is within one step too
        g = jax.random.normal(KEY, (4, 256, 256), jnp.float32)
        rho = jnp.full((4,), 0.25)
        q, s = ops.quantize(g, seed=3, bits=bits)
        fused = ops.dequant_agg(q, s, rho, bits=bits)
        exact = ref.grad_agg_ref(g, rho)
        step = float(jnp.abs(g).max()) / ((1 << (bits - 1)) - 1)
        assert float(jnp.abs(fused - exact).max()) <= step

    def test_codec_and_kernel_share_the_int4_wire_format(self):
        # the codec's flat 256-element tiles are the kernel's (1, 256)
        # tiles of a (1, T, 256) tensor: same words, same scales
        g = jax.random.normal(KEY, (64, 256), jnp.float32)
        p = IntQuantCodec(4, stochastic=False).encode(g)
        q, s = ops.quantize(g[None], bits=4, backend="jnp", block_t=1,
                            stochastic=False)
        np.testing.assert_array_equal(np.asarray(p.data), np.asarray(q[0]))
        np.testing.assert_array_equal(np.asarray(p.scale),
                                      np.asarray(s[0, :, 0]))

    def test_int4_payload_is_half_the_bytes(self):
        g = jax.random.normal(KEY, (2, 256, 256), jnp.float32)
        q8, _ = ops.quantize(g, bits=8)
        q4, _ = ops.quantize(g, bits=4)
        assert q4.size * 2 == q8.size
        assert q4.dtype == jnp.int8


# ------------------------------------------------------- accounting + sim
class TestBitsAccounting:
    def test_int8_ratio_meets_target(self):
        # simulator-scale payload: cut=2 light CNN, batch 32
        numel = 784 * 32
        assert compression_ratio("int8", numel) >= 3.9

    @settings(max_examples=10, deadline=None)
    @given(name=st.sampled_from(["fp32", "bf16", "fp8", "int8", "int4",
                                 "topk10"]), numel=st.integers(1, 10000))
    def test_payload_bits_positive_and_monotone_in_bits(self, name, numel):
        b = payload_bits(name, numel)
        assert b > 0
        assert payload_bits("fp32", numel) == numel * 32

    def test_spec_distortion_ordering(self):
        d = {n: spec_for(n).distortion
             for n in ("fp32", "bf16", "int8", "fp8", "int4")}
        assert d["fp32"] == 0.0
        assert d["fp32"] < d["bf16"] < d["int8"] < d["fp8"] < d["int4"]

    def test_simulator_int8_uplink_end_to_end(self):
        from repro.configs.paper_cnn import LIGHT_CONFIG
        from repro.core.simulator import FedSimulator, SimConfig

        rng = np.random.RandomState(0)
        x = rng.rand(4, 1, 32, 28, 28, 1).astype(np.float32)
        y = rng.randint(0, 10, (4, 1, 32)).astype(np.int32)
        base = FedSimulator(LIGHT_CONFIG, SimConfig(
            scheme="sfl_ga", cut=2, n_clients=4, batch=32), seed=0)
        comp = FedSimulator(LIGHT_CONFIG, SimConfig(
            scheme="sfl_ga", cut=2, n_clients=4, batch=32,
            uplink_codec="int8", downlink_codec="int8"), seed=0)
        mb = base.run_round(x, y)
        mc = comp.run_round(x, y)
        assert np.isfinite(mc["loss"])
        assert mb["bits_up"] / mc["bits_up"] >= 3.9
        assert mb["bits_down"] / mc["bits_down"] >= 3.9
        # compression perturbs but does not break training
        assert abs(mc["loss"] - mb["loss"]) < 0.1 * abs(mb["loss"]) + 0.1

    def test_simulator_passthrough_reproduces_baseline_bitexact(self):
        from repro.configs.paper_cnn import LIGHT_CONFIG
        from repro.core.simulator import FedSimulator, SimConfig

        rng = np.random.RandomState(1)
        x = rng.rand(3, 2, 8, 28, 28, 1).astype(np.float32)
        y = rng.randint(0, 10, (3, 2, 8)).astype(np.int32)
        a = FedSimulator(LIGHT_CONFIG, SimConfig(
            scheme="sfl", cut=1, n_clients=3, batch=8, tau=2), seed=3)
        b = FedSimulator(LIGHT_CONFIG, SimConfig(
            scheme="sfl", cut=1, n_clients=3, batch=8, tau=2,
            uplink_codec="fp32", downlink_codec="fp32"), seed=3)
        for _ in range(2):
            ma = a.run_round(x, y)
            mb = b.run_round(x, y)
        assert ma == mb
        for pa, pb in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
            np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


class TestCCCCodecActions:
    def test_action_space_widens_and_decodes(self):
        from repro.ccc.env import CuttingPointEnv, cnn_env_config

        env = CuttingPointEnv(cnn_env_config(
            horizon=2, batch=8, codecs=("fp32", "int8", "int4")))
        assert env.n_actions == len(env.cfg.phis) * 3
        seen = set()
        for a in range(env.n_actions):
            seen.add(env.decode_action(a))
        assert len(seen) == env.n_actions
        env.reset()
        _, r, _, info = env.step(4)  # v=2, int8
        assert info["codec"] == "int8" and info["v"] == 2
        assert info["bits"] < env.smashed_bits(2, "fp32")

    def test_lower_bits_lower_uplink_cost_higher_gamma(self):
        from repro.ccc.env import CuttingPointEnv, cnn_env_config

        env = CuttingPointEnv(cnn_env_config(
            horizon=2, batch=16, codecs=("fp32", "int4")))
        env.reset()
        g32, chi32, _, _ = env.cost_terms(2, "fp32")
        g4, chi4, _, _ = env.cost_terms(2, "int4")
        assert chi4 <= chi32 + 1e-9  # smaller payload, never slower
        assert g4 > g32  # distortion penalty

    def test_default_env_is_paper_faithful(self):
        from repro.ccc.env import CuttingPointEnv, cnn_env_config

        env = CuttingPointEnv(cnn_env_config(horizon=2, batch=8))
        assert env.n_actions == len(env.cfg.phis)
        v, codec = env.decode_action(0)
        assert (v, codec) == (1, "fp32")
