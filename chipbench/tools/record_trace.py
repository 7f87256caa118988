#!/usr/bin/env python3
"""Record profiler traces on the chip and print how they are laid out.

    python3 chipbench/tools/record_trace.py --out DIR [--workload CELL --seed N --seconds S]

Always records a small trace (a few steps of a tiny split training step
under the harness's ``window`` span) into ``DIR/small.xplane.pb`` — the
fixture of ``tests/test_trace.py``. With ``--workload`` it also runs that
cell with ``--trace 1`` and describes its trace (which it does not keep). For each
trace it prints the planes, their lines, event counts and the most
frequent event names, so the names the reduction keys on can be read.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import run  # noqa: E402


def describe(path: str) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    print(f"== {path} ({os.path.getsize(path)} B)")
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            print(f"  line {line.name!r}: {sum(names.values())} events; "
                  f"top {names.most_common(6)}")


def small(out: Path) -> None:
    import jax
    import jax.numpy as jnp

    d = tempfile.mkdtemp()
    def small_matmul(w, x):
        return jnp.tanh(x @ w) @ w.T

    def small_affine(x):
        return x * 2.0 + 1.0

    f, g = jax.jit(small_matmul), jax.jit(small_affine)
    w = jnp.ones((512, 512)) * 0.01
    x = jnp.ones((256, 512))
    f(w, x).block_until_ready()
    g(x).block_until_ready()
    run.start_trace(d)
    with jax.profiler.TraceAnnotation(run.Spans.NAMES[-1]):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("step"):
                y = f(w, x)
                y = g(y).block_until_ready()
            with jax.profiler.TraceAnnotation("batch prep"):
                x = jnp.asarray(x + 0)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, out / "small.xplane.pb")
    shutil.rmtree(d)
    describe(str(out / "small.xplane.pb"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    import jax

    run.use_cache()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    small(out)
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
        for p in glob.glob(str(keep / "**" / "*.xplane.pb"), recursive=True):
            describe(p)
        shutil.rmtree(keep)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
