"""Benchmark driver — one function per paper figure/table.

Prints ``name,us_per_call,derived`` CSV per the repo convention. Each
"call" is the full benchmark routine; ``derived`` carries the headline
metric(s) the paper figure reports.

A machine-readable ``BENCH_*.json`` is always written (default
``BENCH_local.json``; override with ``--json OUT``, disable with
``--json -``) so every run leaves a perf artifact behind. The JSON
carries a manifest header (schema, git SHA, platform, jax version,
timestamp) plus per-entry wall-clock, matching the ``repro.obs``
provenance fields. ``--only a,b`` filters benchmarks by substring (CI
runs the cheap analytic subset).

Fast mode by default (2-core container); REPRO_BENCH_FULL=1 for
paper-scale rounds/episodes/datasets.
"""
from __future__ import annotations

import argparse
import json
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache


def _bench(name, fn, results):
    t0 = time.time()
    try:
        derived = fn()
        wall = time.time() - t0
        us = wall * 1e6
        print(f"{name},{us:.0f},{derived}")
        results[name] = {"us_per_call": round(us), "wall_s": round(wall, 3),
                         "derived": derived, "status": "ok"}
    except Exception as e:  # pragma: no cover
        traceback.print_exc()
        print(f"{name},-1,ERROR:{type(e).__name__}")
        results[name] = {"us_per_call": -1, "wall_s": round(time.time() - t0, 3),
                         "derived": f"ERROR:{type(e).__name__}",
                         "status": "error"}


def bench_fig3():
    from benchmarks import fig3_convergence_vs_cut as f

    rows = f.run()
    accs = {r["scheme"]: r["final_acc"] for r in rows}
    drifts = {r["scheme"]: r["drift"] for r in rows}
    # headline: acc degrades with v; drift grows with v
    return ("acc_v1=%.3f acc_v4=%.3f sfl_ref=%.3f drift_v1=%.1e drift_v4=%.1e"
            % (accs["sfl_ga_v1"], accs["sfl_ga_v4"], accs["sfl_ref"],
               drifts["sfl_ga_v1"], drifts["sfl_ga_v4"]))


def bench_fig4():
    from benchmarks import fig4_comm_overhead as f

    rows = {r["scheme"]: r for r in f.run()}
    return ("MB/round sfl_ga=%.3f psl=%.3f sfl=%.3f fl=%.3f"
            % tuple(rows[s]["mb_per_round"]
                    for s in ("sfl_ga", "psl", "sfl", "fl")))


def bench_fig5():
    from benchmarks import fig5_latency_schemes as f

    rows = {r["scheme"]: r for r in f.run()}
    return ("s/round sfl_ga=%.3f sfl=%.3f psl=%.3f fl=%.3f"
            % tuple(rows[s]["latency_per_round_s"]
                    for s in ("sfl_ga", "sfl", "psl", "fl")))


def bench_fig6():
    from benchmarks import fig6_resource_strategies as f

    rows = {r["strategy"]: r for r in f.run()}
    a1 = next(v for k, v in rows.items() if k.startswith("algorithm1"))
    fx = rows["fixed_cut_v2_fixed_alloc"]
    rd = rows["random_cut_opt_alloc"]
    return ("latency alg1=%.2f fixed_alloc_v2=%.2f random=%.2f"
            % (a1["latency"], fx["latency"], rd["latency"]))


def bench_fig7():
    from benchmarks import fig7_ddqn_convergence as f

    rows = f.run()
    return " ".join("eps=%g:%.1f->%.1f" % (r["epsilon"], r["first_rewards"],
                                           r["last_rewards"]) for r in rows)


def bench_fig8():
    from benchmarks import fig8_latency_vs_bandwidth as f

    rows = f.run()
    lo, hi = rows[0], rows[-1]
    return ("sfl_ga@5MHz=%.3fs sfl_ga@40MHz=%.3fs fl@40MHz=%.3fs"
            % (lo["sfl_ga"], hi["sfl_ga"], hi["fl"]))


def bench_roofline():
    from benchmarks import roofline as f

    rows = f.load()
    ok = [r for r in rows if r.get("status") == "ok"]
    sk = [r for r in rows if r.get("status") == "skipped"]
    er = [r for r in rows if r.get("status") == "error"]
    if not rows:
        return "no dryrun results (run repro.launch.dryrun --all)"
    bn = {}
    for r in ok:
        bn[r["bottleneck"]] = bn.get(r["bottleneck"], 0) + 1
    return f"cells ok={len(ok)} skipped={len(sk)} err={len(er)} bottlenecks={bn}"


def bench_fig9():
    from benchmarks import fig9_accuracy_vs_bits as f

    rows = {r["codec"]: r for r in f.run()}
    return ("acc fp32=%.3f int8=%.3f int4=%.3f int8_ratio=%.2fx"
            % (rows["fp32"]["final_acc"], rows["int8"]["final_acc"],
               rows["int4"]["final_acc"], rows["int8"]["ratio_vs_fp32"]))


def bench_fig10():
    from benchmarks import fig10_closed_loop as f

    rows = {r["strategy"]: r for r in f.run()}
    dyn = rows["dynamic_ddqn"]
    fx = next(v for k, v in rows.items() if k.startswith("fixed_alloc"))
    return ("acc@budget dyn=%.3f fixed_alloc=%.3f dyn_wall=%.1fs "
            "fixed_alloc_wall=%.1fs migrations=%d migrated_mb=%.1f"
            % (dyn["acc_at_budget"], fx["acc_at_budget"],
               dyn["wall_clock_s"], fx["wall_clock_s"],
               dyn["n_migrations"], dyn["migration_mb"]))


def bench_fig11():
    from benchmarks import fig11_scale as f

    rows = f.run()
    worst = max(r["round_ms_vs_baseline"] for r in rows[1:])
    flat = all(r["server_bytes_flat"] for r in rows)
    big = rows[-1]
    return ("server_one_copy=%s worst_ratio=%.2fx N=%d round_ms=%.0f "
            "server_kb=%d" % (flat, worst, big["n_clients"],
                              big["round_ms"], big["server_bytes"] // 1024))


def bench_fig11_bank_host():
    """Host-resident bank scale gate (DESIGN.md §15): N=100k, K=16 —
    peak device client-state bytes must stay within 2× the K-slice."""
    from benchmarks import fig11_scale as f

    r = f.run_smoke()
    if not r["ok"]:
        raise AssertionError(
            f"peak device client-state {r['device_bytes_peak']} B over the "
            f"{r['budget_bytes']} B budget (2x K-slice)")
    return ("N=%d K=%d peak_device_b=%d budget_b=%d bank_mb=%.0f "
            "prefetch_hit=%d miss=%d round_ms=%.0f"
            % (r["n_clients"], r["cohort"], r["device_bytes_peak"],
               r["budget_bytes"], r["bank_bytes"] / 1e6,
               r["prefetch_hits"], r["prefetch_misses"], r["round_ms"]))


def bench_fig12():
    """Buffered-async vs barrier (DESIGN.md §16): accuracy at the
    matched virtual-clock budget, exact traffic reconciliation on both
    loops (the async split of the sysmodel rows must price to the
    measured ledger bit for bit)."""
    from benchmarks import fig12_async as f

    rows = {r["scheme"]: r for r in f.run()}
    if any(not r["traffic_ok"] for r in rows.values()):
        raise AssertionError("async/sync traffic reconciliation mismatch")
    ga = rows["sfl_ga"]
    return ("acc@budget async=%.3f sync=%.3f merges=%d sync_clock=%.1fs "
            "staleness=%.2f traffic_exact=True"
            % (ga["async_acc_at_budget"], ga["sync_acc_at_budget"],
               ga["async_merges"], ga["sync_clock_s"],
               ga["mean_staleness"]))


def bench_fig13():
    """PEFT federation (DESIGN.md §17): full-granite-8b wire + migration
    ratios vs LoRA rank (rank-8 must clear the 20x wire / 50x migration
    bars) and a live reduced LoRA run reconciled exactly."""
    from benchmarks import fig13_peft as f

    out = f.run()
    live = out["live"]
    return ("r8_wire=%.0fx r8_migration=%.0fx live_events=%d "
            "live_migrations=%d reconcile_exact=True"
            % (out["wire_ratio_r8"], out["migration_ratio_r8"],
               live["events"], live["migrations"]))


def bench_kernels():
    from benchmarks import kernels_bench as f

    rows = f.run()
    return " ".join(f"{n}={us:.0f}us" for n, us in rows)


def bench_serve():
    """Continuous-batching split decode (DESIGN.md §18): aggregate tok/s
    vs the fixed-batch sequential baseline at equal slot count with a
    heavy-tailed queue — must clear 2x — plus p50/p99 per-token latency
    and exact decode/prefill traffic reconciliation."""
    from benchmarks import serve_bench as f

    out = f.run()
    assert out["traffic_mismatches"] == 0, \
        f"serve traffic ledger mismatches: {out['traffic_mismatches']}"
    assert out["speedup"] >= 2.0, \
        f"continuous batching speedup {out['speedup']:.2f}x < 2x gate"
    cont = next(r for r in out["rows"] if r["scheduler"] == "continuous"
                and r["users"] == max(r2["users"] for r2 in out["rows"]))
    return ("speedup=%.2fx cont_tok_s=%.0f p50_ms=%.1f p99_ms=%.1f "
            "slo=%.3f traffic_events=%d reconcile_exact=True"
            % (out["speedup"], cont["tok_per_s"], cont["p50_s"] * 1e3,
               cont["p99_s"] * 1e3, cont["slo_attainment"],
               out["traffic_events"]))


BENCHES = [
    ("kernels_micro", bench_kernels),
    ("fig8_latency_vs_bandwidth", bench_fig8),
    ("roofline_table", bench_roofline),
    ("fig6_resource_strategies", bench_fig6),
    ("fig7_ddqn_convergence", bench_fig7),
    ("fig3_convergence_vs_cut", bench_fig3),
    ("fig4_comm_overhead", bench_fig4),
    ("fig5_latency_schemes", bench_fig5),
    ("fig9_accuracy_vs_bits", bench_fig9),
    ("fig10_closed_loop", bench_fig10),
    ("fig11_scale", bench_fig11),
    ("fig11_scale_bank_host", bench_fig11_bank_host),
    ("fig12_async", bench_fig12),
    ("fig13_peft", bench_fig13),
    ("serve_continuous_batching", bench_serve),
]


def _manifest() -> dict:
    """Provenance header matching repro.obs manifests — same fields, so a
    BENCH_*.json and a metrics dir from the same commit line up."""
    import platform
    import sys

    from repro.obs import recorder as _rec

    man = {"schema": "repro.bench.v1",
           "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "git_sha": _rec.git_sha(),
           "platform": platform.platform(),
           "python": sys.version.split()[0]}
    import jax

    man["jax_version"] = jax.__version__
    man["backend"] = jax.default_backend()
    return man


def main(argv=None) -> int:
    """Runs the selected benches; returns 1 if any of them raised."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default="BENCH_local.json", metavar="OUT",
                    help="JSON artifact path (default BENCH_local.json; "
                         "'-' disables)")
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings: run matching benches only")
    args = ap.parse_args(argv)
    enable_compile_cache()
    wanted = [w for w in (args.only or "").split(",") if w]
    results = {}
    print("name,us_per_call,derived")
    for name, fn in BENCHES:
        if wanted and not any(w in name for w in wanted):
            continue
        _bench(name, fn, results)
    if args.json and args.json != "-":
        with open(args.json, "w") as f:
            json.dump({"manifest": _manifest(), "results": results},
                      f, indent=2, sort_keys=True)
        print(f"# json -> {args.json}")
    failed = [n for n, r in results.items() if r["status"] != "ok"]
    if failed:
        print(f"# failed: {','.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
