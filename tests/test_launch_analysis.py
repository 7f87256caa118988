"""Launch-layer units: sharding rules, comm models, and the trip-count-aware
HLO roofline parser (exact counts on a synthetic module)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.analysis.roofline import analyze_hlo
from repro.launch import shardings as shd
from repro.sysmodel.comm import CommParams, uplink_rate

SYNTH_HLO = """
HloModule synth

%cond (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %k = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16] get-tuple-element(%p), index=1
  %w = f32[16,16] constant({...})
  %d = f32[8,16] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16] all-reduce(%d), replica_groups={}, to_apply=%add
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,16]) tuple(%ni, %ar)
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,16]) tuple(%zero, %a)
  %w = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  %out = f32[8,16] get-tuple-element(%w), index=1
  %ag = f32[64,16] all-gather(%out), dimensions={0}, replica_groups={}
  %red = f32[8,16] slice(%ag), slice={[0:8], [0:16]}
  ROOT %r = f32[8,16] add(%red, %out)
}
"""


class TestRooflineParser:
    def test_trip_count_multiplied(self):
        s = analyze_hlo(SYNTH_HLO)
        # dot: 2 * 8*16 (result) * 16 (contracted) = 4096 flops, x5 trips
        assert s.flops == 5 * 2 * 8 * 16 * 16
        # all-reduce f32[8,16] = 512 B x5; all-gather result f32[64,16]=4096 B
        assert s.coll_bytes_by_kind["all-reduce"] == 5 * 512
        assert s.coll_bytes_by_kind["all-gather"] == 4096
        assert s.coll_count_by_kind["all-reduce"] == 5

    def test_real_artifact_parses(self):
        """The granite-8b HLO dumped during the perf work, if present."""
        import os

        if not os.path.exists("/tmp/g8b_train.hlo"):
            pytest.skip("no dumped artifact")
        s = analyze_hlo(open("/tmp/g8b_train.hlo").read())
        assert s.flops > 1e14  # trip-count aware (34-layer scan)
        assert s.coll_bytes > 1e10


class TestShardingRules:
    def setup_method(self):
        self.mesh = jax.make_mesh((1, 1), ("data", "model"))

    def _spec(self, name_path, shape, client=False, **kw):
        class K:  # fake DictKey
            def __init__(self, k):
                self.key = k

        path = tuple(K(n) for n in name_path)
        leaf = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        return shd.param_spec(path, leaf, mesh=self.mesh, client=client, **kw)

    def test_column_parallel(self):
        assert self._spec(("attn", "wq", "w"), (512, 512)) == P(None, "model")

    def test_row_parallel(self):
        assert self._spec(("attn", "wo", "w"), (512, 512)) == P("model", None)

    def test_client_leading_axis(self):
        s = self._spec(("groups", "attn", "wq", "w"), (4, 2, 512, 512),
                       client=True)
        assert s[0] == "data"

    def test_norms_replicated(self):
        assert self._spec(("norm1", "scale"), (512,)) == P(None)

    def test_expert_parallel_layout(self):
        s = self._spec(("moe", "w_gate"), (8, 512, 256), expert_parallel=True)
        assert s == P("data", "model", None)  # E over data, d over model
        s = self._spec(("moe", "w_down"), (8, 256, 512), expert_parallel=True)
        assert s == P("data", None, "model")

    def test_indivisible_replicates(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        s = self._spec(("attn", "wk", "w"), (513, 127))
        assert all(x is None or x == "model" for x in tuple(s) + (None,))


class TestCommModel:
    @settings(max_examples=20, deadline=None)
    @given(bw=st.floats(1e4, 1e8), g_db=st.floats(-130.0, -60.0))
    def test_rate_positive_and_saturating(self, bw, g_db):
        p = CommParams()
        g = 10 ** (g_db / 10)
        r1 = uplink_rate(np.array([bw]), p.client_power, np.array([g]), p)
        r2 = uplink_rate(np.array([bw * 2]), p.client_power, np.array([g]), p)
        assert r1[0] >= 0
        assert r2[0] >= r1[0] - 1e-9  # monotone in bandwidth
        # saturation bound: r <= p*g/(N0 ln2)
        cap = p.client_power * g / (p.noise_psd * np.log(2))
        assert r1[0] <= cap * (1 + 1e-9)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, compiles land there and the
    launchers set no other cache; without it, the cache is the fixed
    ``<checkout>/.jax_cache``. A child process: JAX settles its cache once
    per process."""
    import json
    import os
    import subprocess
    import sys

    script = (
        "import json, jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,\n"
        "                                        enable_compile_cache)\n"
        "where = enable_compile_cache()\n"
        "if COMPILE:\n"
        "    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64)))\n"
        "print(json.dumps({'where': where,\n"
        "                  'config': jax.config.jax_compilation_cache_dir,\n"
        "                  'checkout': str(CHECKOUT_CACHE_DIR)}))\n"
    ).replace("COMPILE", str(from_env))
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=src, JAX_PLATFORMS="cpu")
    cache = tmp_path / "cache"
    if from_env:
        env.update(JAX_COMPILATION_CACHE_DIR=str(cache),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    if from_env:
        assert got["where"] == got["config"] == str(cache)
        assert any(cache.iterdir())
    else:
        assert got["where"] == got["config"] == got["checkout"]
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got["checkout"] == os.path.join(root, ".jax_cache")
