"""The correctness check must fail what it is there to catch.

The control — the plain reference with bfloat16 activations and float32
parameters, put in the program's place — must read past a limit of each
cell, and a run driven
with the timed path broken underneath must come out not correct: a step
that returns its state unchanged, half of the batch left out of the mean,
a served token altered where it is produced. Small sizes, on the CPU, with
each cell's own limits (``chipbench/limits/``)."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests import small
from chipbench.tools import calibrate

TRAIN = "train-granite8b-k4"
SERVE = {"serve-granite8b-online": (small.CONFIG, small.ONLINE),
         "serve-granite20b-offline": (small.CONFIG_GELU, small.OFFLINE)}


def _cell(workload, config, traffic, seed=11):
    cell = calibrate.cell_for(workload, seed, 1.0)
    cell.config, cell.traffic = config, traffic
    return cell


def _fails(workload, checks):
    """Whether the cell's compared numbers (those its limits name) fail."""
    limits = run.resolve(workload)["limits"]
    _, ok = run.compare([(k, checks[k]) for k in limits], limits)
    return not ok


def test_control_fails_training():
    res = calibrate.train_readings(_cell(TRAIN, small.CONFIG, small.TRAIN))
    assert not _fails(TRAIN, res["program"])
    assert _fails(TRAIN, res["control"])
    assert _fails(TRAIN, res["half_batch"])


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("workload", sorted(SERVE))
def test_control_fails_serving(workload, seed):
    cell = _cell(workload, small.CONFIG_CHECK, small.CHECK, seed)
    cell.seconds = 2.0
    res = calibrate.serve_readings(cell)
    assert not _fails(workload, res["program"])
    assert _fails(workload, res["control"])


def _run(workload, config, traffic):
    return run.execute(workload, 5, 1.0, False, config=config,
                       traffic=traffic, peak_kind="TPU v5 lite")


def _broken_train_step(monkeypatch, fault):
    from repro.core import algorithms as alg

    real = alg.make_train_step

    def make(*a, **k):
        step = real(*a, **k)

        def broken(params, opt_state, batch):
            if fault == "half_batch":
                lab = batch["labels"]
                keep = jnp.arange(lab.shape[0]) < lab.shape[0] // 2
                batch = dict(batch, labels=jnp.where(
                    keep[:, None, None], lab, -1))  # -1: left out of CE
                return step(params, opt_state, batch)
            _, _, m = step(params, opt_state, batch)
            return params, opt_state, m  # state unchanged

        return broken

    monkeypatch.setattr(alg, "make_train_step", make)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_training_is_not_correct(monkeypatch, fault):
    assert _run(TRAIN, small.CONFIG, small.TRAIN)["correct"]
    _broken_train_step(monkeypatch, fault)
    assert not _run(TRAIN, small.CONFIG, small.TRAIN)["correct"]


def _broken_decode(monkeypatch, fault):
    from repro.core.serve_engine import ServeEngine

    real = ServeEngine._build_step

    def build(self):
        step = real(self)
        vocab = self.cfg.vocab_size

        def broken(params, caches, tokens, live, key, seed):
            nxt, new = step(params, caches, tokens, live, key, seed)
            if fault == "token_altered":
                return (nxt + 1) % vocab, new
            return nxt, caches  # state unchanged: the cache never grows

        return broken

    monkeypatch.setattr(ServeEngine, "_build_step", build)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
@pytest.mark.parametrize("workload", sorted(SERVE))
def test_broken_serving_is_not_correct(monkeypatch, workload, fault):
    _broken_decode(monkeypatch, fault)
    assert not _run(workload, *SERVE[workload])["correct"]


def test_reference_attention_paths_agree():
    """Query blocks and decode-order rows compute the causal attention that
    one full softmax does (float32 on the CPU: to rounding)."""
    from chipbench.reference import dense_split_lm as ref

    T, page = 2 * ref.Q_BLOCK, 16
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, T, 4, 32))
    k = jax.random.normal(ks[1], (1, T, 2, 32))
    v = jax.random.normal(ks[2], (1, T, 2, 32))
    full = ref._attend(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), 0)
    blocks = ref.attention(q, k, v)
    decode = ref.attention(q, k, v, (page, T // 3))
    assert jnp.allclose(blocks, full.reshape(1, T, -1), atol=1e-5)
    assert jnp.allclose(decode, full.reshape(1, T, -1), atol=1e-5)
