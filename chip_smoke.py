#!/usr/bin/env python3
"""Drive the repo's main paths once on a TPU, through the entry points a
user calls, and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded client bank

One chip runs these phases, in order:

* device  — JAX version and the devices JAX sees; anything but a TPU
  fails here (there is no CPU fallback).
* kernels — every Pallas kernel of ``kernels/ops.py`` against its jnp
  oracle at granite-8b widths; each lowered program must hold the Mosaic
  kernel (``tpu_custom_call``), i.e. the kernel was compiled, not
  interpreted.
* train   — ``repro.launch.train``: split LoRA fine-tuning of granite-8b at
  its published widths, depth cut to 4 layers, int8 boundary, cohort 4 of
  16 clients; four finite losses and a clean strict ``repro.obs.report``.
* serve   — ``repro.launch.serve``: paged continuous-batching decode of
  the same model with the Pallas paged-attention kernel, strict report;
  then the same requests on the jnp attention path, printing the share of
  greedy tokens that agree (information, not a gate).
* cnn     — ``repro.launch.train --arch paper-cnn``: a few SFL-GA rounds
  with an int8 uplink on the device client bank.

``--chips 4`` runs only the paper-cnn comparison of ``--bank sharded``
against ``--bank device`` at 64 clients, cohort 8: per-round losses of
both, per-device bank bytes and memory.

Everything runs in this one process: a chip belongs to one process, so
this starts no children. The compile cache follows
``repro.launch.compile_cache``. The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every phase
passed; otherwise the exit code is non-zero. Longer output (the strict
reports, obs events) goes under ``--out``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import shutil
import sys
import time
import traceback
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# granite-8b (arXiv:2405.04324) at its published widths
ARCH = "granite-8b"
HQ, HKV, HEAD_DIM, D_MODEL = 32, 8, 128, 4096
# the train phase's split boundary: K clients x b sequences x S tokens
COHORT, BATCH, SEQ = 4, 1, 512

# Gates on max|kernel - oracle| / max|oracle|, over unit-normal inputs.
# On the TPU an f32 matmul at default precision passes its operands
# through the MXU as bf16 (unit roundoff 2^-9 ~ 2e-3), and the kernel and
# its oracle round at different points (online vs one-shot softmax, a VPU
# multiply-add vs an einsum), so they agree to a few roundoffs, not bit
# for bit. A wrong mask, page or tile moves the output by O(1).
ATTN_TOL = 2e-2   # scores and probabilities both rounded, errors pass exp
REDUCE_TOL = 1e-2  # one bf16 rounding per operand, at most, on either side
# The quantizer's oracle is built to match it bit for bit: the same
# counter hash, the same tiles. A code may still move by one step where a
# value sits on a rounding boundary and the chip divides differently;
# scales are a max times a constant, exact on any backend.
QUANT_STEP_TOL = 1
# Sharded vs device bank: the same f32 arithmetic, placed differently, so
# XLA may partition and reorder reductions (2^-24 each) and the gap grows
# through the rounds of SGD; a wrong client row moves a loss by ~1e-2.
BANK_LOSS_RTOL = 1e-4


def _memory(devices) -> str:
    """Peak and limit of device memory, as each device reports them."""
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"dev{d.id} peak {st.get('peak_bytes_in_use')} B "
                     f"in use {st.get('bytes_in_use')} B "
                     f"of {st.get('bytes_limit')} B")
    return "; ".join(parts)


def _rel_diff(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _round_losses(run_dir: Path) -> list:
    from repro.obs.recorder import read_events

    return [e["loss"] for e in read_events(str(run_dir))
            if e.get("kind") == "round"]


def _strict_report(run_dir: Path) -> None:
    """``python -m repro.obs.report --strict`` in process; the rendered
    report goes to ``report.txt`` beside the events."""
    from repro.obs import report

    with open(run_dir / "report.txt", "w") as f, \
            contextlib.redirect_stdout(f):
        rc = report.main([str(run_dir), "--strict"])
    last = (run_dir / "report.txt").read_text().strip().splitlines()
    recon = [ln.strip() for ln in last if "reconcile" in ln]
    print(f"  strict report rc={rc}: {recon[-1] if recon else '-'}")
    if rc != 0:
        raise RuntimeError(f"strict obs report failed (rc={rc}) for "
                           f"{run_dir}; see report.txt")


# ------------------------------------------------------------------ phases
def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.ref import unpack_int4_ref

    ks = iter(jax.random.split(jax.random.key(0), 16))

    def normal(shape):
        return jax.random.normal(next(ks), shape, jnp.float32)

    def check(name, fn, args, tol, compare=_rel_diff):
        kern = jax.jit(partial(fn, backend="pallas"))
        lowered = kern.lower(*args)
        compiled = "tpu_custom_call" in lowered.as_text()
        a = jax.block_until_ready(kern(*args))
        b = jax.block_until_ready(jax.jit(partial(fn, backend="jnp"))(*args))
        d = compare(a, b)
        print(f"  {name:<22} diff {d!r} (gate {tol})  "
              f"tpu_custom_call={compiled}")
        if not compiled:
            raise RuntimeError(f"{name}: no tpu_custom_call in the lowered "
                               f"program — the kernel was interpreted")
        if not d <= tol:
            raise RuntimeError(f"{name}: kernel vs oracle {d!r} > {tol}")

    # prefill-shaped causal attention over one 512-token sequence
    q = normal((1, SEQ, HQ, HEAD_DIM))
    k = normal((1, SEQ, HKV, HEAD_DIM))
    v = normal((1, SEQ, HKV, HEAD_DIM))
    check("flash_attention", ops.flash_attention, (q, k, v), ATTN_TOL)

    # batched decode: 4 slots of up to 160 tokens in pages of 16, pages
    # scattered over the pool, one slot retired (length 0)
    slots, page, max_pages = 4, 16, 10
    pool = (HKV, slots * max_pages, page, HEAD_DIM)
    table = jax.random.permutation(next(ks), slots * max_pages)
    table = table.reshape(slots, max_pages).astype(jnp.int32)
    lengths = jnp.asarray([160, 97, 17, 0], jnp.int32)
    check("paged_attention", ops.paged_attention,
          (normal((slots, HQ, HEAD_DIM)), normal(pool), normal(pool), table,
           lengths), ATTN_TOL)

    # the boundary: K clients' (b·S, d_model) smashed data
    g = normal((COHORT, BATCH * SEQ, D_MODEL))
    rho = jax.nn.softmax(normal((COHORT,)))
    check("grad_agg", ops.grad_agg, (g, rho), REDUCE_TOL)

    for bits in (8, 4):
        def codes(out, bits=bits):
            q, _ = out
            if bits == 4:  # compare values, not packed words
                q = unpack_int4_ref(q)
            return np.asarray(q).astype(np.int32)

        def quant_compare(a, b):
            if not np.array_equal(np.asarray(a[1]), np.asarray(b[1])):
                raise RuntimeError(f"int{bits} scales differ: max rel "
                                   f"{_rel_diff(a[1], b[1])!r}")
            qa, qb = codes(a), codes(b)
            moved = int(np.sum(qa != qb))
            print(f"  int{bits} codes: {moved} of {qa.size} differ from the "
                  f"oracle (bit-identical: {moved == 0})")
            return int(np.max(np.abs(qa - qb)))

        check(f"quantize int{bits}",
              lambda x, backend, bits=bits: ops.quantize(
                  x, seed=7, bits=bits, backend=backend),
              (g,), QUANT_STEP_TOL, compare=quant_compare)
        qz, sc = ops.quantize(g, seed=7, bits=bits, backend="jnp")
        check(f"dequant_agg int{bits}",
              lambda q_, s_, r_, backend, bits=bits: ops.dequant_agg(
                  q_, s_, r_, bits=bits, backend=backend),
              (qz, sc, rho), REDUCE_TOL)


def phase_train(out: Path) -> None:
    from repro.launch import train

    run = out / "train"
    shutil.rmtree(run, ignore_errors=True)
    res = train.main([
        "--arch", ARCH, "--preset", "full", "--layers", "4", "--cut", "1",
        "--scheme", "sfl_ga", "--peft", "lora", "--lora-rank", "8",
        "--uplink-codec", "int8", "--downlink-codec", "int8",
        "--clients", "16", "--cohort", str(COHORT), "--batch", str(BATCH),
        "--seq", str(SEQ), "--steps", "4", "--log-every", "1",
        "--metrics-dir", str(run)])
    losses = _round_losses(run)
    print(f"  losses {losses}")
    print(f"  comm/round {res['comm']['total_bytes']} B")
    if len(losses) != 4 or not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"train: expected 4 finite losses, got {losses}")
    _strict_report(run)


def phase_serve(out: Path) -> None:
    from repro.launch import serve

    run = out / "serve"
    shutil.rmtree(run, ignore_errors=True)
    argv = ["--arch", ARCH, "--preset", "full", "--layers", "4",
            "--users", "8", "--slots", "4", "--prompt-len", "128",
            "--gen", "32", "--codec", "int8", "--page-size", "16"]
    engine = serve.main(argv + ["--attn-impl", "flash",
                                "--metrics-dir", str(run)])
    _strict_report(run)
    compiled = "tpu_custom_call" in engine.lower_step().as_text()
    print(f"  paged decode step: tpu_custom_call={compiled}")
    if not compiled:
        raise RuntimeError("serve: no tpu_custom_call in the lowered decode "
                           "step — the paged-attention kernel was not "
                           "compiled into it")
    flash = engine.completions
    del engine
    gc.collect()  # the first engine's weights sit in a reference cycle
    plain = serve.main(argv + ["--attn-impl", "jnp", "--quiet"]).completions
    ref = {c.uid: c.tokens for c in plain}
    same = total = 0
    for c in flash:
        total += len(c.tokens)
        same += sum(a == b for a, b in zip(c.tokens, ref[c.uid]))
    print(f"  served {len(flash)} users / {total} tokens; greedy tokens "
          f"matching the jnp path: {same}/{total} = {same / total!r}")
    for c in flash[:2]:
        print(f"  uid {c.uid}: flash {c.tokens[:12]} jnp {ref[c.uid][:12]}")
    if total != 8 * 32:
        raise RuntimeError(f"serve: expected 256 tokens, got {total}")


def phase_cnn(out: Path) -> None:
    from repro.launch import train

    run = out / "cnn"
    shutil.rmtree(run, ignore_errors=True)
    res = train.main(["--arch", "paper-cnn", "--scheme", "sfl_ga",
                      "--uplink-codec", "int8", "--bank", "device",
                      "--rounds", "5", "--log-every", "1",
                      "--metrics-dir", str(run)])
    losses = _round_losses(run)
    print(f"  losses {losses}  accuracy {res['accuracy']!r}")
    if len(losses) != 5 or not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"cnn: expected 5 finite losses, got {losses}")
    _strict_report(run)


def phase_bank4(out: Path) -> None:
    import jax

    from repro.launch import train

    common = ["--arch", "paper-cnn", "--scheme", "sfl_ga", "--clients", "64",
              "--cohort", "8", "--rounds", "5", "--log-every", "1"]
    runs = {}
    for bank in ("sharded", "device"):
        run = out / f"cnn_{bank}"
        shutil.rmtree(run, ignore_errors=True)
        res = train.main(common + ["--bank", bank, "--metrics-dir", str(run)])
        runs[bank] = _round_losses(run)
        print(f"  {bank}: losses {runs[bank]}")
        if bank == "sharded":
            per = res["bank"]["bytes_by_device"]
            print(f"  sharded bank bytes by device {per} "
                  f"(bank {res['bank']['bank_bytes']} B)")
            if len(per) != len(jax.devices()):
                raise RuntimeError(f"sharded bank rows sit on {len(per)} of "
                                   f"{len(jax.devices())} devices: {per}")
        gc.collect()
        print(f"  memory after {bank}: {_memory(jax.devices())}")
    s, d = runs["sharded"], runs["device"]
    if len(s) != 5 or len(d) != 5:
        raise RuntimeError(f"expected 5 rounds each, got {s} / {d}")
    gap = max(abs(a - b) / max(abs(b), 1e-30) for a, b in zip(s, d))
    print(f"  sharded vs device: max rel loss gap {gap!r} (gate "
          f"{BANK_LOSS_RTOL}); bit-identical: {s == d}")
    if not gap <= BANK_LOSS_RTOL:
        raise RuntimeError(f"sharded vs device losses differ by {gap!r}")


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-vs-device bank comparison")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="where reports and obs events are written")
    args = ap.parse_args(argv)

    import jax

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(f"jax {jax.__version__}; compile cache {cache} "
          f"({n_cached} entries at start)")

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform {dev.platform} kind {dev.device_kind} "
          f"count {len(devices)}")
    if dev.platform != "tpu":
        print(f"error: no TPU found (JAX sees {dev.platform}); this smoke "
              f"test runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"error: --chips {args.chips} but JAX sees {len(devices)}",
              file=sys.stderr)
        return 2

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    if args.chips == 4:
        phases = [("bank4", partial(phase_bank4, out))]
    else:
        phases = [("kernels", phase_kernels),
                  ("train", partial(phase_train, out)),
                  ("serve", partial(phase_serve, out)),
                  ("cnn", partial(phase_cnn, out))]
    failed = []
    t_all = time.perf_counter()
    for name, fn in phases:
        print(f"== {name}", flush=True)
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        dt = time.perf_counter() - t0
        # free the phase's device arrays before the next phase allocates:
        # launcher objects (engines, jitted closures) form reference cycles
        gc.collect()
        print(f"[{name}] {'FAILED' if name in failed else 'ok'} in "
              f"{dt:.2f} s wall (bring-up observation, compile included); "
              f"{_memory(devices[:args.chips])}", flush=True)
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(f"all phases {time.perf_counter() - t_all:.2f} s wall; compile "
          f"cache {n_cached} entries at end")
    if failed:
        print(f"error: failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
