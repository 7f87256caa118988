#!/usr/bin/env python3
"""Offered-load sweep of an open-loop serving cell, to find its knee.

    python3 chipbench/tools/sweep.py --workload <cell> --rates 8,12,16 [--seconds S]

Runs the cell's own traffic at each arrival rate in turn (one process, one
run per rate, the seed fixed) and prints one JSON line per rate: tokens per
second, the tails of time to first token and of the gap between tokens, and
the requests due, admitted and still queued at the window's end. The knee is
the highest rate whose queue does not grow through the window.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import run  # noqa: E402
from chipbench.tools.calibrate import cell_for  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax

    run.use_cache()
    if jax.devices()[0].platform != "tpu":
        print("error: the sweep runs on the chip", file=sys.stderr)
        return 3
    serve = run.load_module("drivers", "serve")
    for rate in (float(r) for r in args.rates.split(",")):
        cell = cell_for(args.workload, args.seed, args.seconds)
        cell.traffic = dict(cell.traffic, arrivals=dict(
            cell.traffic["arrivals"], rate=rate))
        out = serve.run(cell)
        c = out["counts"]
        print(json.dumps({"rate": rate, **out["e2e"],
                          "queue_wait_p95_ms": 1e3 * float(np.percentile(
                              c["queue_wait_s"], 95))
                          if c["queue_wait_s"] else None,
                          "due": c["due"], "admitted": c["admitted"],
                          "queue_at_end": c["queue_at_end"],
                          "engine_steps": c["engine_steps"],
                          "checks": dict(out["checks"])}), flush=True)
        del cell, out
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
