"""What the train step hands back (DESIGN.md §17).

With a PEFT plan the step returns only the trainable ``{client, server}``
adapter tree; the frozen ``base`` is an input only, so the compiled step
neither copies it nor allocates an output for it. The trained adapters
are bit-identical to those of a step that hands the base back, and the
full-parameter step is untouched: its jaxpr equals that of the step as it
was written before PEFT steps dropped the base."""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.drivers.train import model_config
from chipbench.tests import small
from repro.configs import TrainConfig, get_config, reduced_config
from repro.configs.base import PeftSpec
from repro.core import algorithms as alg
from repro.core.gradagg import uniform_rho
from repro.core.protocol import aggregate_cohort
from repro.models import lm
from repro.optim import make_optimizer
from repro.optim.optimizers import apply_updates

CONFIGS = {"dense": small.CONFIG, "hybrid": small.CONFIG_HYBRID}
N, K, B, S = 6, 2, 1, 32
IDX = np.array([4, 1])
RANK, ALPHA, LR = 4, 8.0, 0.05
# a copy of an entry parameter of the compiled program, by its HLO name
COPY = re.compile(r"= (\S+) copy\(%([^)]+)\)")


def _parent_train_step(plan, tcfg, opt, n_clients):
    """The train step as written before a PEFT step stopped returning its
    frozen base: the PEFT local step returns ``dict(tr, base=base)`` and
    the τ-step scan carries the whole tree."""
    rho = uniform_rho(n_clients)
    engine = alg._engine_for(tcfg)
    loss_fn = alg.make_loss_fn(plan, tcfg, rho, engine=engine)
    tau = tcfg.resolved_tau

    if plan.peft is None:
        def local_step(params, opt_state, batch, seed, w):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, seed, w)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return params, opt_state, dict(metrics, loss=loss)
    else:
        def tr_loss(tr, base, batch, seed, w):
            return loss_fn(dict(tr, base=base), batch, seed, w)

        def local_step(params, opt_state, batch, seed, w):
            base = params["base"]
            tr = {k: v for k, v in params.items() if k != "base"}
            (loss, metrics), grads = jax.value_and_grad(
                tr_loss, has_aux=True)(tr, base, batch, seed, w)
            updates, opt_state = opt.update(grads, opt_state, tr)
            tr = apply_updates(tr, updates)
            return dict(tr, base=base), opt_state, dict(metrics, loss=loss)

    def train_step(params, opt_state, batch):
        seed = batch.get("seed", 0)
        w = batch.get("rho")
        client0 = jax.tree.map(lambda x: x[0], params["client"]) \
            if (w is not None and engine.spec.client_aggregate) else None
        if tau == 1:
            params, opt_state, metrics = local_step(params, opt_state,
                                                    batch, seed, w)
        else:
            xs = jnp.moveaxis(batch["tokens"], 1, 0)
            ys = jnp.moveaxis(batch["labels"], 1, 0)
            seeds = engine.epoch_seeds(seed, xs.shape[0])

            def body(carry, sl):
                p, s = carry
                t, l, sd = sl
                p, s, m = local_step(p, s, {"tokens": t, "labels": l}, sd, w)
                return (p, s), m

            (params, opt_state), ms = jax.lax.scan(
                body, (params, opt_state), (xs, ys, seeds))
            metrics = jax.tree.map(jnp.mean, ms)
        if engine.spec.client_aggregate:
            engine.tap_model_sync(params["client"])
            if w is None:
                client = engine.aggregate(params["client"], rho)
            else:
                agg = aggregate_cohort(params["client"], w, anchor=client0)
                client = jax.tree.map(
                    lambda a, like: jnp.broadcast_to(
                        a[None], like.shape).astype(like.dtype),
                    agg, params["client"])
            params = dict(params, client=client)
        return params, opt_state, metrics

    return train_step


@functools.lru_cache(maxsize=None)
def _case(name, tau, peft=True, algo="sfl_ga"):
    """The benchmark's cohort path on a small configuration: a bank of N
    clients, the K-row cohort gathered from it, and one batch."""
    cfg = model_config(CONFIGS[name])
    spec = PeftSpec(kind="lora", rank=RANK, alpha=ALPHA) if peft else None
    plan = lm.build_plan(cfg, 1, peft=spec)
    tcfg = TrainConfig(model=cfg, algo=algo, cut_layer=1,
                       compute_dtype="float32", param_dtype="float32",
                       lr=LR, remat=False, tau=tau, uplink_codec="int8",
                       downlink_codec="int8",
                       peft="lora" if peft else "none", lora_rank=RANK,
                       lora_alpha=ALPHA, seed=0)
    base = lm.init_lm(jax.random.key(0), plan, jnp.float32)
    if peft:
        # nonzero B factors, so that every adapter leaf gets a gradient
        loras = jax.tree.map(
            lambda x: x + 0.01 if x.ndim >= 2 else x,
            lm.init_lm_loras(jax.random.key(1), plan, jnp.float32))
        bank = alg.split_lm_lora_params(base, loras, N)
    else:
        bank = alg.split_lm_params(base, N)
    opt = make_optimizer("sgd", LR)
    bank_opt = opt.init(alg.trainable_params(bank))
    shape = (K, B, S) if tau == 1 else (K, tau, B, S)
    rng = np.random.RandomState(tau)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, shape)),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, shape)),
             "seed": jnp.uint32(12345),
             "rho": jnp.full((K,), 1.0 / N, jnp.float32)}
    return dict(plan=plan, tcfg=tcfg, opt=opt, bank=bank,
                bank_opt=bank_opt, cp=alg.gather_cohort(bank, IDX),
                cop=alg.gather_cohort_opt(bank_opt, IDX), batch=batch)


@functools.lru_cache(maxsize=None)
def _compiled(name, tau):
    c = _case(name, tau)
    step = jax.jit(alg.make_train_step(c["plan"], c["tcfg"], c["opt"], K))
    return step.lower(c["cp"], c["cop"], c["batch"]).compile()


@functools.lru_cache(maxsize=None)
def _trained(name, tau):
    c = _case(name, tau)
    return _compiled(name, tau)(c["cp"], c["cop"], c["batch"])


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


CASES = pytest.mark.parametrize("name,tau", [(n, t) for n in CONFIGS
                                             for t in (1, 2)])


@CASES
def test_peft_step_returns_the_trainable_tree_only(name, tau):
    new, opt_state, metrics = _trained(name, tau)
    assert set(new) == {"client", "server"}
    c = _case(name, tau)
    assert (jax.tree.structure(new)
            == jax.tree.structure(alg.trainable_params(c["cp"])))
    assert np.isfinite(float(metrics["loss"]))


@CASES
def test_compiled_peft_step_copies_no_base_leaf(name, tau):
    text = _compiled(name, tau).as_text()
    entry = text[text.index("ENTRY"):]
    params = re.findall(r"%(params__\S+) = \S+ parameter\(", entry)
    # the entry's parameters carry the names the regex looks for
    assert len([p for p in params if p.startswith("params__base")]) \
        == len(jax.tree.leaves(_case(name, tau)["cp"]["base"]))
    copied = [src for _, src in COPY.findall(entry)
              if src.startswith("params__base")]
    assert copied == []


@CASES
def test_peft_step_output_is_smaller_than_the_base(name, tau):
    out = _compiled(name, tau).memory_analysis().output_size_in_bytes
    assert out < _nbytes(_case(name, tau)["cp"]["base"])


@CASES
def test_gather_step_scatter_keeps_the_banks_base(name, tau):
    c = _case(name, tau)
    assert c["cp"]["base"] is c["bank"]["base"]
    cp, cop, _ = _trained(name, tau)
    new = alg.scatter_cohort(c["bank"], cp, IDX)
    assert new["base"] is c["bank"]["base"]
    new_opt = alg.scatter_cohort_opt(c["bank_opt"], cop, IDX)
    assert (jax.tree.structure(new_opt)
            == jax.tree.structure(c["bank_opt"]))
    # the scattered rows are the trained ones
    for x, y in zip(jax.tree.leaves(new["client"]),
                    jax.tree.leaves(cp["client"])):
        np.testing.assert_array_equal(np.asarray(x)[IDX], np.asarray(y))


@CASES
def test_trained_adapters_equal_the_base_returning_steps(name, tau):
    c = _case(name, tau)
    old = jax.jit(_parent_train_step(c["plan"], c["tcfg"], c["opt"], K))
    want, want_opt, want_m = old(c["cp"], c["cop"], c["batch"])
    got, got_opt, got_m = _trained(name, tau)
    assert set(want) == {"client", "server", "base"}
    for part in ("client", "server"):
        assert jax.tree.structure(got[part]) == jax.tree.structure(want[part])
        for x, y in zip(jax.tree.leaves(got[part]),
                        jax.tree.leaves(want[part])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves((got_opt, got_m)),
                    jax.tree.leaves((want_opt, want_m))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("algo", ["sfl_ga", "sfl"])
@CASES
def test_full_parameter_step_jaxpr_unchanged(name, tau, algo):
    c = _case(name, tau, peft=False, algo=algo)
    args = (c["cp"], c["cop"], c["batch"])
    new = alg.make_train_step(c["plan"], c["tcfg"], c["opt"], K)
    old = _parent_train_step(c["plan"], c["tcfg"], c["opt"], K)
    assert "base" not in c["cp"]
    assert str(jax.make_jaxpr(new)(*args)) == str(jax.make_jaxpr(old)(*args))


@pytest.mark.parametrize("tau", [1, 2])
def test_launcher_full_participation_keeps_the_base(tmp_path, tau):
    """The launcher's ``--peft lora`` loop without ``--cohort`` feeds the
    step's output back as its next input: the base must survive it."""
    from repro.checkpoint import load_checkpoint
    from repro.launch.train import main

    n, seed = 2, 3
    ck = os.path.join(tmp_path, "lora.ckpt")
    main(["--arch", "granite-8b", "--preset", "smoke", "--layers", "3",
          "--peft", "lora", "--lora-rank", "8", "--scheme", "sfl",
          "--clients", str(n), "--batch", "1", "--seq", "16",
          "--tau", str(tau), "--steps", "2", "--seed", str(seed),
          "--checkpoint", ck, "--quiet"])
    cfg = reduced_config(get_config("granite-8b")).with_overrides(
        num_layers=3)
    plan = lm.build_plan(cfg, 1, peft=PeftSpec(kind="lora", rank=8,
                                               alpha=16.0))
    base = lm.init_lm(jax.random.key(seed), plan, jnp.float32)
    loras = lm.init_lm_loras(jax.random.fold_in(jax.random.key(seed), 1),
                             plan, jnp.float32)
    init = alg.split_lm_lora_params(base, loras, n)
    opt = make_optimizer("sgd", LR).init(alg.trainable_params(init))
    state, meta = load_checkpoint(ck, {"params": init, "opt": opt})
    assert meta["step"] == 2
    params = state["params"]
    assert set(params) == {"client", "server", "base"}
    assert jax.tree.structure(params["base"]) == jax.tree.structure(base)
    for x, y in zip(jax.tree.leaves(params["base"]), jax.tree.leaves(base)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # two steps ran: the server's adapters moved off their start
    moved = [not np.array_equal(np.asarray(x), np.asarray(y))
             for x, y in zip(jax.tree.leaves(params["server"]),
                             jax.tree.leaves(init["server"]))]
    assert any(moved)
