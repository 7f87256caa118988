"""Each driver end to end on the CPU at a small size, called from the test
(the command line itself refuses a device that is not a TPU), and the
traffic generator's determinism in ``--seed``."""
import json

import numpy as np
import pytest

from chipbench import generate, run
from chipbench.tests import small

BIG_SEED = 2**31 + 12345  # seeds may run past 32 signed bits


def _execute(cell, config, traffic, seed=BIG_SEED, trace=False):
    return run.execute(cell, seed, 1.0, trace, config=config,
                       traffic=traffic, peak_kind="TPU v5 lite")


@pytest.mark.parametrize("cell,config,traffic,metric", [
    ("train-granite8b-k4", small.CONFIG, small.TRAIN, "train_step_ms"),
    ("serve-granite8b-online", small.CONFIG, small.ONLINE, "ttft_p95_ms"),
    ("serve-granite20b-offline", small.CONFIG_GELU, small.OFFLINE,
     "serve_tokens_per_s"),
])
def test_driver_runs_and_agrees_with_reference(cell, config, traffic,
                                               metric):
    res = _execute(cell, config, traffic)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"][metric]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0


def test_traced_run_reports_per_layer_metrics_only():
    res = _execute("serve-granite8b-online", small.CONFIG, small.ONLINE,
                   trace=True)
    assert set(res["metrics"]) == {"queue_wait_p95_ms.online"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    json.dumps(res)


def test_command_line_refuses_the_cpu(capsys):
    rc = run.main(["--workload", "train-granite8b-k4", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("traffic", [small.ONLINE, small.OFFLINE])
def test_generator_is_deterministic_in_seed(traffic):
    a = generate.requests(traffic, BIG_SEED, 2.0)
    b = generate.requests(traffic, BIG_SEED, 2.0)
    c = generate.requests(traffic, 7, 2.0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    # another seed: the same sizes and gaps, in another order
    np.testing.assert_array_equal(np.sort(a[1]), np.sort(c[1]))
    np.testing.assert_array_equal(np.sort(a[2]), np.sort(c[2]))
    np.testing.assert_allclose(np.sort(np.diff(a[0], prepend=0)),
                               np.sort(np.diff(c[0], prepend=0)))
    assert not np.array_equal(a[1], c[1])
    np.testing.assert_array_equal(generate.prompt_tokens(BIG_SEED, 3, 16, 512),
                                  generate.prompt_tokens(BIG_SEED, 3, 16, 512))
    t1 = generate.token_batch(BIG_SEED, 5, (2, 1, 8), 512)
    t2 = generate.token_batch(BIG_SEED, 5, (2, 1, 8), 512)
    np.testing.assert_array_equal(t1[0], t2[0])
    assert not np.array_equal(t1[0][0], t1[0][1])


def test_lengths_follow_the_law():
    x = generate.lengths({"law": "lognormal", "median": 160, "sigma": 0.9,
                          "buckets": [64, 128, 256, 512, 1024]}, 2000, 1,
                         "prompt")
    assert set(np.unique(x)) <= {64, 128, 256, 512, 1024}
    y = generate.lengths({"law": "lognormal", "median": 64, "sigma": 0.8,
                          "min": 8, "max": 512}, 2001, 1, "output")
    assert y.min() >= 8 and y.max() <= 512 and np.median(y) == 64


@pytest.mark.parametrize("ahead_s", [0.0, 1e9])
def test_window_counts_every_step_it_dispatched(monkeypatch, ahead_s):
    """The training window reads the loss of every step it dispatched, the
    ones still in flight when its time is up too: with every window loss
    made NaN, each dispatched step is attempted and failed, whether the
    losses are read one step late or all at the end."""
    import jax.numpy as jnp

    from chipbench.tools import calibrate

    train = run.load_module("drivers", "train")
    monkeypatch.setattr(train, "AHEAD_S", ahead_s)
    real, calls = train.Setup.one, []

    def one(self, i):
        loss, fed = real(self, i)
        if i < self.cell.traffic["check_steps"]:
            return loss, fed
        calls.append(i)
        return jnp.float32(np.nan), fed

    monkeypatch.setattr(train.Setup, "one", one)
    cell = calibrate.cell_for("train-granite8b-k4", BIG_SEED, 0.5)
    cell.config, cell.traffic = small.CONFIG, small.TRAIN
    out = train.run(cell)
    assert len(calls) > 0
    assert out["attempted"] == out["failed"] == len(calls)
    assert out["counts"]["steps"] == len(calls)
