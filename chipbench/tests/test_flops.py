"""The needed-FLOP counts of ``chipbench/flops.py`` against XLA's own count
of the program's compiled step, at a small size on the CPU.

XLA counts what the program computes (full attention squares, the loop body
of a scan once), so the needed count must not exceed it, and sits close to
it where matmuls dominate. Two layers keep every scan at one trip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, weights
from chipbench.drivers.train import model_config
from chipbench.tests import small
from repro.models.transformer import layer_specs

CFG = dict(small.CONFIG, num_layers=2, d_ff=1024, vocab_size=2048)


def _xla_flops(compiled):
    ca = compiled.cost_analysis()
    return (ca[0] if isinstance(ca, list) else ca)["flops"]


def plain_layers(cfg):
    """Each layer's weight shapes in the plain layout, as the driver hands
    them to ``train_step_flops``."""
    from repro.models import lm

    base = jax.eval_shape(lambda k: lm.init_lm(k, lm.build_plan(cfg, 1)),
                          jax.random.key(0))
    return jax.eval_shape(weights.plain, base)["layers"]


def xla_step_flops(cfg, K, b, S, r):
    """XLA's count of the program's compiled split LoRA step (cut 1)."""
    from repro.configs.base import PeftSpec, TrainConfig
    from repro.core import algorithms as alg
    from repro.models import lm
    from repro.optim import make_optimizer

    plan = lm.build_plan(cfg, 1, peft=PeftSpec("lora", r, 8.0))
    tcfg = TrainConfig(model=cfg, algo="sfl_ga", cut_layer=1,
                       compute_dtype="float32", param_dtype="float32",
                       lr=0.05, remat=False, tau=1, uplink_codec="int8",
                       downlink_codec="int8", peft="lora", lora_rank=r,
                       lora_alpha=8.0)
    opt = make_optimizer("sgd", 0.05)
    p = jax.eval_shape(lambda k: alg.split_lm_lora_params(
        lm.init_lm(k, plan), lm.init_lm_loras(k, plan), K), jax.random.key(0))
    o = jax.eval_shape(lambda p: opt.init(alg.trainable_params(p)), p)
    i32 = jax.ShapeDtypeStruct((K, b, S), jnp.int32)
    bt = {"tokens": i32, "labels": i32,
          "seed": jax.ShapeDtypeStruct((), jnp.uint32),
          "rho": jax.ShapeDtypeStruct((K,), jnp.float32)}
    return _xla_flops(jax.jit(alg.make_train_step(plan, tcfg, opt, K))
                      .lower(p, o, bt).compile())


def test_train_step_flops_within_xla_count():
    K, b, S, r = 2, 1, 128, 4
    cfg = model_config(CFG)
    xla = xla_step_flops(cfg, K, b, S, r)
    need = flops.train_step_flops(CFG, plain_layers(cfg),
                                  kinds=layer_specs(cfg), cohort=K, batch=b,
                                  seq=S, rank=r)
    assert 0.85 * xla <= need <= xla


@pytest.fixture(scope="module")
def engine():
    from repro.core.serve_engine import ServeEngine
    from repro.models import lm

    cfg = model_config(CFG)
    plan = lm.build_plan(cfg, 1)
    params = lm.init_lm(jax.random.key(0), plan)
    return ServeEngine(params, plan, slots=4, max_len=64, page_size=8,
                       codec="int8")


def test_decode_step_flops_within_xla_count(engine):
    # every slot live at the full length: what the jnp paged path computes
    xla = _xla_flops(engine.lower_step().compile())
    need, nbytes = flops.decode_step(CFG, [64] * 4)
    assert 0.85 * xla <= need <= xla
    assert nbytes >= flops.weight_bytes(CFG)


def test_prefill_flops_within_xla_count(engine):
    S = 48
    xla = _xla_flops(engine._prefill_fn.lower(
        engine.params, jnp.zeros((1, S), jnp.int32), jax.random.key(0),
        jnp.uint32(0)).compile())
    need, _ = flops.prefill(CFG, S)
    assert 0.85 * xla <= need <= xla


def test_least_time_takes_the_larger_bound():
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert flops.least_s(2e12, 1e9, peak) == 2.0
    assert flops.least_s(1e12, 3e9, peak) == 3.0
    assert np.isclose(flops.least_s(0, 1e9, peak), 1.0)
