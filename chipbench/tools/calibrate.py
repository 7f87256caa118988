#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python3 chipbench/tools/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds S]

For each seed, in one process: the program's readings (as a benchmark run
takes them), the control's (the plain reference with bfloat16 activations
and float32 parameters, put in the program's place) and, for a training
cell, a planted fault's (the reference with half of the cohort's rows left
out of the loss). Each reading is compared with the float32 reference
exactly as a run compares the program. A training cell needs no measured window; a serving cell runs
``--seconds`` of its traffic so that its sample holds what a run's does.
One JSON line per seed goes to standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import run  # noqa: E402


def cell_for(workload: str, seed: int, seconds: float):
    r = run.resolve(workload)
    import jax

    devices = jax.devices()[:r["cell"]["chips"]]
    return SimpleNamespace(
        name=workload, config=r["config"], traffic=r["traffic"], seed=seed,
        seconds=seconds, trace_dir=None, spans=run.Spans(False),
        compiles=run.Compiles(), t_process=time.perf_counter(),
        devices=devices,
        reference=run.load_module("reference", r["config"]["reference"]),
        memory_peak=lambda: run.memory_peak(devices),
        start_trace=run.start_trace)


def train_readings(cell) -> dict:
    import jax.numpy as jnp

    train = run.load_module("drivers", "train")
    st = train.Setup(cell)
    prog = train.program_readings(st)
    st.drop()
    base, snap0, fed, lr = st.base, st.snaps[0], st.fed, st.lr
    del st
    ref = train.reference_run(cell, base, snap0, fed, lr)
    out = {"program": dict(train.compare(prog, ref))}
    ctrl = train.reference_run(cell, base, snap0, fed, lr, jnp.bfloat16)
    out["control"] = dict(train.compare(ctrl, ref))
    half = train.reference_run(cell, base, snap0, fed, lr, half=True)
    out["half_batch"] = dict(train.compare(half, ref))
    return out


def serve_readings(cell) -> dict:
    serve = run.load_module("drivers", "serve")
    keep = {}
    out = serve.run(cell, keep=keep)
    res = {"program": dict(keep["readings"]), "e2e": out["e2e"]}
    res["control"] = dict(serve.check(cell, *keep["args"], control=True))
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    import jax

    run.use_cache()
    if jax.devices()[0].platform != "tpu":
        print("error: calibration readings are taken on the chip",
              file=sys.stderr)
        return 3
    driver = run.resolve(args.workload)["traffic"]["driver"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        cell = cell_for(args.workload, seed, args.seconds)
        res = (train_readings if driver == "train" else serve_readings)(cell)
        res.update(workload=args.workload, seed=seed,
                   seconds=time.perf_counter() - t)
        print(json.dumps(res), flush=True)
        del cell
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
