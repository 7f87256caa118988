"""The trace reduction on a small trace recorded on a TPU v5e
(``data/small.xplane.pb``, written by ``tools/record_trace.py``: three
rounds of two small jitted programs under the harness's ``window`` span),
and its interval arithmetic on hand-made intervals."""
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return trace.load(str(DATA))


def test_chip_trace_has_a_device_and_the_window(tr):
    assert len(tr.devices) == 1
    assert 0 < tr.window_s < 5
    assert 0 < trace.busy_s(tr) < tr.window_s
    assert 0 < trace.idle_share(tr) < 100


def test_chip_trace_programs_by_name(tr):
    # three runs of each were launched inside the window; on this trace the
    # device clock reads about 1.4 ms behind the host's, so the first run
    # of each lands just before the window's start
    counts = trace.module_counts(tr)
    assert counts.get("small_matmul") in (2, 3)
    assert counts.get("small_affine") in (2, 3)
    both = trace.module_s(tr, {"small_matmul", "small_affine"})
    rest = trace.module_s(tr, {"small_matmul", "small_affine"}, exclude=True)
    assert both > 0
    assert both + (rest or 0.0) <= tr.window_s


def test_chip_trace_breakdown(tr):
    ops = trace.top_ops(tr)
    assert 0 < len(ops) <= 10
    assert sum(s for _, s in ops) <= trace.busy_s(tr) + 1e-9
    gaps = trace.idle_gaps(tr, ("step", "batch prep", "window"))
    idle = tr.window_s - trace.busy_s(tr)
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6)


def test_module_name_strips_jit_and_id():
    assert trace.module_name("jit_train_step(12)") == "train_step"
    assert trace.module_name("jit_step") == "step"


def test_busy_is_the_union_clipped_to_the_window():
    dev = trace.Device(ops=[("a", 0.0, 2.0), ("b", 1.0, 3.0),
                            ("c", 5.0, 6.0), ("d", 9.0, 12.0)])
    tr = trace.Trace((1.0, 10.0), {"/device:TPU:0": dev},
                     [("window", 1.0, 10.0), ("step", 4.0, 7.0)])
    assert trace.busy_s(tr) == pytest.approx(2.0 + 1.0 + 1.0)
    assert trace.idle_share(tr) == pytest.approx(100 * (1 - 4.0 / 9.0))
    gaps = dict(trace.idle_gaps(tr, ("step", "window")))
    assert gaps == pytest.approx({"step": 2.0, "window": 3.0})


def test_mfu_train_takes_the_step_time_from_the_device():
    from types import SimpleNamespace

    from chipbench import run

    mfu = run.load_module("metrics", "mfu.train")
    dev = trace.Device(modules=[("jit_train_step(3)", 1.0, 1.5),
                                ("jit_gather(4)", 1.5, 2.0),
                                ("jit_train_step(3)", 4.0, 4.5)])
    tr = trace.Trace((0.0, 10.0), {"/device:TPU:0": dev}, [])
    ctx = SimpleNamespace(trace=tr, counts={"step_flops": 1e12},
                          peak={"flops_per_s": 4e12})
    # two steps of 0.5 s on the device: 1e12 / (0.5 s x 4e12) = 50 %
    assert mfu.read(ctx) == pytest.approx(50.0)
    none = trace.Trace((0.0, 10.0), {"/device:TPU:0": trace.Device()}, [])
    assert mfu.read(SimpleNamespace(**dict(vars(ctx), trace=none))) is None
