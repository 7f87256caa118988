"""The client bank's readers (``program_spans`` and the three
``bank_*.train`` metrics) on a small trace recorded on a TPU v5e with the
program's own spans (``data/bank.xplane.pb.gz``, written by
``tools/record_bank_trace.py``: two steps of the training driver at the
``small.py`` sizes, gzipped, since each step's 294 eager programs bring
their HLO text and the raw trace is 2.5 MB), on ``data/small.xplane.pb``,
which holds no program span, and on hand-made intervals."""
import gzip
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import program_spans, run, trace

DATA = Path(__file__).parent / "data"
METRICS = ("bank_host_ms.train", "bank_dispatches.train",
           "bank_idle_ms.train")
STEPS = 2  # in the recorded window


def _read(name, tr):
    return run.load_module("metrics", name).read(SimpleNamespace(trace=tr))


@pytest.fixture(scope="module")
def bank(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "bank.xplane.pb"
    with gzip.open(DATA / "bank.xplane.pb.gz") as src, open(path, "wb") as f:
        shutil.copyfileobj(src, f)
    return trace.load(str(path))


def test_chip_trace_holds_the_steps_and_the_bank_spans(bank):
    assert trace.module_counts(bank)[program_spans.STEP] == STEPS
    names = {h[0] for h in bank.host}
    assert {"bank/gather", "bank/scatter"} <= names
    # the program's spans never take a name the harness reads
    ours = {n for n in names if n.startswith(program_spans.BANK)}
    assert not ours & set(run.Spans.NAMES)


def test_dispatches_are_the_programs_besides_the_step(bank):
    counts = trace.module_counts(bank)
    other = sum(v for k, v in counts.items() if k != program_spans.STEP)
    assert other > 0
    assert _read("bank_dispatches.train", bank) == other / STEPS


def test_bank_host_time_lies_inside_the_harness_spans(bank):
    harness = sum(e - s for n, s, e in bank.host
                  if n in ("cohort gather", "scatter"))
    host = _read("bank_host_ms.train", bank) * STEPS / 1e3
    assert 0.9 * harness <= host <= harness


def test_bank_idle_is_at_most_bank_host_time(bank):
    idle = _read("bank_idle_ms.train", bank)
    assert 0 <= idle <= _read("bank_host_ms.train", bank)


@pytest.mark.parametrize("metric", METRICS)
def test_readers_find_nothing_without_program_spans(metric):
    assert _read(metric, trace.load(str(DATA / "small.xplane.pb"))) is None


def test_readers_on_hand_made_intervals():
    step = "jit_train_step(7)"
    dev = trace.Device(ops=[("op", 2.0, 4.0), ("op", 6.5, 9.0)],
                       modules=[(step, 2.0, 4.0), (step, 6.5, 9.0)])
    ex = program_spans.EXECUTE
    tr = trace.Trace((0.0, 10.0), {"/device:TPU:0": dev}, [
        ("window", 0.0, 10.0), ("cohort gather", 0.9, 3.1),
        ("bank/gather", 1.0, 3.0), ("bank/gather", 1.5, 2.5),  # nested
        ("bank/scatter", 6.0, 7.0), ("bank/drain", 9.5, 11.0),  # clipped
        (ex, 1.2, 1.3), (ex, 2.9, 3.2), (ex, 4.5, 4.6), (ex, 9.6, 9.7),
        (ex, 10.5, 10.6)])
    # bank: [1, 3] + [6, 7] + [9.5, 10] = 3.5 s over two steps
    assert _read("bank_host_ms.train", tr) == pytest.approx(1750.0)
    # launched inside the bank: 1.2, 2.9, 9.6 (4.5 is outside, 10.5 past
    # the window)
    assert _read("bank_dispatches.train", tr) == pytest.approx(1.5)
    # the device ran in [2, 3] and [6.5, 7] of the bank's 3.5 s
    assert _read("bank_idle_ms.train", tr) == pytest.approx(1000.0)
    no_step = trace.Trace(tr.window, {"/device:TPU:0": trace.Device(
        ops=dev.ops)}, tr.host)
    for m in METRICS:
        assert _read(m, no_step) is None
