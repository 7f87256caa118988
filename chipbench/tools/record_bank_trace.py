#!/usr/bin/env python3
"""Record the client bank's program spans on the chip, and check the readers.

    python3 chipbench/tools/record_bank_trace.py --out DIR [--workload CELL --seed N --seconds S]

Always records ``DIR/bank.xplane.pb``, the fixture of
``tests/test_bank_spans.py``: two steps of the training driver at the
``tests/small.py`` sizes under the harness's ``window`` span, with the
program's own ``bank/*`` spans, and a probe of whether a span's attributes
change its event's name. That window opens a few milliseconds before the
first step and closes once the device has run every program launched in it,
so that on the device's clock, which may lag the host's by a millisecond or
two, each of those programs lies inside it. With ``--workload`` it also runs that cell traced
(as ``--trace 1`` does), and prints the three bank readings beside what each
should agree with, per step: the harness's ``cohort gather`` and ``scatter``
spans, and the device's executions of programs other than ``train_step``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

from chipbench import program_spans, run, trace  # noqa: E402
from chipbench.tools.record_trace import describe  # noqa: E402

STEPS = 2
LEAD_S = 0.005


def _traced(fn) -> str:
    """Runs ``fn`` under the harness's profiler; the trace's directory."""
    import jax

    d = tempfile.mkdtemp()
    run.start_trace(d)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return d


def _pb(d: str) -> str:
    return glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]


def small(out: Path, seed: int) -> None:
    from chipbench.drivers import train
    from chipbench.tests import small as sizes
    from repro import obs

    cell = SimpleNamespace(config=sizes.CONFIG, traffic=sizes.TRAIN,
                           seed=seed, spans=run.Spans(True))
    st = train.Setup(cell)  # compiles the loop through its first steps
    first = len(st.fed)

    def steps():
        import jax

        with cell.spans(trace.WINDOW_SPAN):
            time.sleep(LEAD_S)
            for i in range(STEPS):
                st.one(first + i)
            jax.block_until_ready((st.params, st.opt_state))

    d = _traced(steps)
    shutil.copy(_pb(d), out / "bank.xplane.pb")
    shutil.rmtree(d)
    describe(str(out / "bank.xplane.pb"))
    report(trace.load(str(out / "bank.xplane.pb")))

    def probe():
        with obs.null_recorder.span("attr-probe", cut=1):
            pass

    from jax.profiler import ProfileData

    d = _traced(probe)
    names = [e.name for p in ProfileData.from_file(_pb(d)).planes
             for line in p.lines for e in line.events
             if e.name.startswith("attr-probe")]
    shutil.rmtree(d)
    print(f"a span with attributes reads as {names!r}")


def report(tr: trace.Trace) -> None:
    """The readers beside the numbers they should agree with."""
    counts = trace.module_counts(tr)
    n = counts.get(program_spans.STEP) or 0
    harness = sum(e - s for name, s, e in trace._clip(tr.host, *tr.window)
                  if name in ("cohort gather", "scatter"))
    other = sum(v for k, v in counts.items() if k != program_spans.STEP)
    print(json.dumps({
        "steps": n,
        "bank_host_ms": program_spans.host_ms(tr),
        "harness_gather_scatter_ms": 1e3 * harness / n if n else None,
        "bank_dispatches": program_spans.dispatches(tr),
        "other_programs_per_step": other / n if n else None,
        "bank_idle_ms": program_spans.idle_ms(tr),
        "idle_gaps": trace.idle_gaps(tr, run.Spans.NAMES)}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    run.use_cache()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    small(out, args.seed)
    if args.workload:
        keep = Path(tempfile.mkdtemp())
        real_mkdtemp, real_rmtree = tempfile.mkdtemp, shutil.rmtree
        tempfile.mkdtemp = lambda *a, **k: str(keep)
        shutil.rmtree = lambda *a, **k: None
        try:
            res = run.execute(args.workload, args.seed, args.seconds, True)
        finally:
            tempfile.mkdtemp, shutil.rmtree = real_mkdtemp, real_rmtree
        print(json.dumps(res))
        report(trace.load(str(keep)))
        describe(_pb(str(keep)))
        shutil.rmtree(keep)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
