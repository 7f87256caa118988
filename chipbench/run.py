#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json``, one run, one result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json`` names its
configuration (``chipbench/configs/<config>.json``) and its traffic mix
(``chipbench/traffic/<traffic>.json``); the mix names its driver
(``chipbench/drivers/<driver>.py``); the configuration names its plain
reference (``chipbench/reference/<reference>.py``); each per-layer metric is
read by ``chipbench/metrics/<metric>.py``; the limits of the correctness
comparison are ``chipbench/limits/<cell>.json``.

The run makes its weights and inputs from ``--seed``, warms up every shape
the cell uses (set-up), measures for ``--seconds`` (with ``--trace 1``, for
at most ``TRACE_SECONDS``, under the profiler), then compares what the timed
path produced with the plain reference. The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` ``breakdown``) and last ``checks``, each
number compared beside its limit; the same numbers end standard error.

There is no CPU fallback: without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
CACHE_DIR = CHECKOUT / ".chipbench_cache"
TRACE_SECONDS = 10.0

if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))
if str(CHECKOUT / "src") not in sys.path:
    sys.path.insert(0, str(CHECKOUT / "src"))


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str) -> dict:
    with open(HERE / kind / f"{name}.json") as f:
        return json.load(f)


def bench() -> dict:
    with open(CHECKOUT / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(workload: str) -> dict:
    """The cell, its configuration, traffic, limits and metric entries."""
    b = bench()
    cells = {w["name"]: w for w in b["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in b["configs"]}[cell["config"]]
    with open(CHECKOUT / conf["file"]) as f:
        config = json.load(f)
    limits_path = HERE / "limits" / f"{workload}.json"
    limits = json.loads(limits_path.read_text()) if limits_path.exists() \
        else {}

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {"cell": cell, "config": config,
            "traffic": load_json("traffic", cell["traffic"]),
            "limits": limits,
            "end_to_end": [m for m in b["end_to_end"] if applies(m)],
            "per_layer": [m for m in b["per_layer"] if applies(m)]}


# ------------------------------------------------------------ run context
class Spans:
    """The harness's own host spans: under ``--trace 1`` each is a
    ``jax.profiler.TraceAnnotation``, so the trace can name idle gaps by
    what the host was doing; otherwise a span costs one branch."""

    NAMES = ("batch prep", "cohort gather", "step", "scatter", "loss read",
             "generator", "admit/engine step", "window")

    def __init__(self, on: bool):
        self.on = on

    @contextmanager
    def __call__(self, name: str):
        if not self.on:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield


class Compiles:
    """Counts JAX traces, compiles and cache reads while ``active``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1


def use_cache() -> None:
    """The compile cache lives in the checkout, at a fixed path of its own,
    whatever the machine's environment says; small programs are cached too."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def start_trace(path: str) -> None:
    """The profiler without its Python call tracer: device activity and the
    harness's spans only."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(path, profiler_options=opts)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def compare(readings, limits):
    """The readings that the cell's limits name, each beside its limit, and
    whether all are within them. A named reading that the run did not
    produce fails; the other readings are shown on standard error only."""
    got = dict(readings)
    out, ok = {}, bool(limits)
    for name, lim in limits.items():
        value = got.get(name)
        ok = ok and value is not None and math.isfinite(value) \
            and value <= lim
        out[name] = {"value": value, "limit": lim}
    for name in sorted(set(got) - set(limits)):
        print(f"reading {name}: {got[name]!r} (shown, not compared)",
              file=sys.stderr)
    return out, ok


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            devices=None, config=None, traffic=None, limits=None,
            peak_kind=None) -> dict:
    """One run of ``workload``; returns the result object. The keyword
    arguments let tests substitute small sizes and skip the chip."""
    import jax

    r = resolve(workload)
    if config is not None:
        r["config"] = config
    if traffic is not None:
        r["traffic"] = traffic
    if limits is not None:
        r["limits"] = limits
    devices = devices if devices is not None else jax.devices()
    devices = devices[:r["cell"]["chips"]]
    kind = peak_kind or devices[0].device_kind
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    driver = load_module("drivers", r["traffic"]["driver"])
    reference = load_module("reference", r["config"]["reference"])
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    cell = SimpleNamespace(
        name=workload, config=r["config"], traffic=r["traffic"], seed=seed,
        seconds=min(seconds, TRACE_SECONDS) if trace else seconds,
        trace_dir=tdir, spans=Spans(trace), compiles=Compiles(),
        t_process=T_PROCESS, devices=devices, reference=reference,
        memory_peak=lambda: memory_peak(devices), start_trace=start_trace)
    try:
        out = driver.run(cell)
        metrics, breakdown, dev = {}, None, {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": out["memory_peak"]}
        if trace:
            from chipbench import trace as tracemod

            tr = tracemod.load(tdir)
            dev["busy_s"] = tracemod.busy_s(tr)
            dev["window_s"] = tr.window_s
            breakdown = {"device_ops": tracemod.top_ops(tr),
                         "idle_gaps": tracemod.idle_gaps(tr, Spans.NAMES)}
            ctx = SimpleNamespace(trace=tr, config=r["config"],
                                  traffic=r["traffic"], peak=peaks[kind],
                                  counts=out["counts"])
            for m in r["per_layer"]:
                v = load_module("metrics", m["name"]).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            print(f"modules run in the traced window: "
                  f"{tracemod.module_counts(tr)}", file=sys.stderr)
        else:
            for m in r["end_to_end"]:
                metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                      "unit": m["unit"]}
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    checks, correct = compare(out["checks"], r["limits"])
    res = {"correct": correct, "attempted": out["attempted"],
           "failed": out["failed"], "metrics": metrics, "device": dev}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["checks"] = checks
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (CHECKOUT / "src" / "repro").is_dir():
        print(f"error: the system under test ({CHECKOUT / 'src'}) is not in "
              f"this checkout", file=sys.stderr)
        return 2
    chips = resolve(args.workload)["cell"]["chips"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"error: no TPU (JAX sees {devices[0].platform}); the "
              f"benchmark runs only on the chip", file=sys.stderr)
        return 3
    if len(devices) < chips:
        print(f"error: {args.workload} needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    use_cache()
    res = execute(args.workload, args.seed, args.seconds, bool(args.trace))
    line = json.dumps(res)
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
