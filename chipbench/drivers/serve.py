"""Driver of split serving: ``ServeEngine`` under an open loop or a backlog.

The harness owns the clock and the queue's inflow: each request is handed to
``engine.submit`` at its due time (or all at t = 0 for a backlog), and the
loop calls ``engine.step()`` while the engine has work. ``step()`` admits
into free slots (prefill-on-admit, ``adopt`` into the paged cache), decodes
one token for every live slot and retires finished requests; its tokens
reach the host when it returns, so a token's time is the end of the step
that produced it.

Set-up makes the weights, warms every shape the mix uses (each prompt
bucket's prefill and adopt, the decode step, the table push), then runs
``warmup_s`` of the same traffic so the window starts in steady state.

After the window the program's state is dropped and the plain reference
scores a sample of the finished requests, drawn from the seed with the
longest among them: one full forward over prompt and served tokens, each
boundary activation quantized with the seed and payload offset that the
wire used for it, and the widest gap by which a served token's logit lies
below the reference's best at its position. The control reads the same
gap for the token that the reference in bfloat16 puts first.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from chipbench import generate, weights
from chipbench.drivers.train import model_config, reference_config

PREFILL_SEED, DECODE_SEED = 0x9E37, 0x51E9


def run(cell, keep=None) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.serve_engine import Request, ServeEngine
    from repro.models import lm

    cfgj, tj, seed = cell.config, cell.traffic, cell.seed
    spans = cell.spans
    cfg = model_config(cfgj)
    plan = lm.build_plan(cfg, cfgj["cut"])
    dtype = jnp.dtype(cfgj["dtype"])
    params = weights.make(jax.eval_shape(
        lambda k: lm.init_lm(k, plan, dtype), jax.random.key(0)), seed,
        ssm=cfg.ssm)
    engine_seed = int(generate.rng(seed, "order").integers(0, 2**31))
    engine = ServeEngine(params, plan, slots=tj["slots"],
                         max_len=tj["max_len"], page_size=tj["page_size"],
                         codec=cfgj["boundary_codec"], temperature=0.0,
                         seed=engine_seed, dtype=dtype)
    V = cfg.vocab_size
    due, plen, out = generate.requests(tj, seed, cell.seconds)

    # ---- set-up: every shape of the mix, then warm-up traffic
    for j, L in enumerate(sorted(set(tj["prompt"]["buckets"]))):
        engine.submit(Request(uid=-1 - j, prompt=generate.prompt_tokens(
            seed, 10**9 + j, L, V), max_new_tokens=2))
    engine.run()
    # what set-up made stays alive through the window: keep it out of the
    # collector's way, so that no collection of it lands inside the window
    import gc
    gc.collect()
    gc.freeze()
    pauses = []                      # the collector's pauses, on stderr

    def on_gc(phase, info):
        if phase == "start":
            pauses.append([time.perf_counter(), 0.0])
        elif pauses:
            pauses[-1][1] = time.perf_counter() - pauses[-1][0]

    gc.callbacks.append(on_gc)

    comp_of = {}                     # uid -> Completion (live object)
    slot_of = {}                     # uid -> decode slot
    t_start, t_end = {}, {}          # engine step index -> host times
    admits = {}                      # engine step index -> admissions
    late = []
    n_admitted = n_done = len(engine.completions)
    i, n = 0, len(due)
    T0 = time.perf_counter()
    t_w0 = t_w1 = None
    k0 = k1 = None
    warm = tj["warmup_s"]
    while True:
        now = time.perf_counter()
        if t_w0 is None and now - T0 >= warm:
            t_w0, k0 = now, engine.step_count
            cell.compiles.active = True
            if cell.trace_dir:
                cell.start_trace(cell.trace_dir)
                win = spans("window")
                win.__enter__()
        if t_w0 is not None and now - t_w0 >= cell.seconds:
            t_w1, k1 = now, engine.step_count
            break
        with spans("generator"):
            while i < n and T0 + due[i] <= now:
                engine.submit(Request(uid=i, prompt=generate.prompt_tokens(
                    seed, i, int(plen[i]), V), max_new_tokens=int(out[i])))
                late.append(now - (T0 + due[i]))
                i += 1
        if engine.queue or n_admitted > len(engine.completions):
            k = engine.step_count
            with spans("admit/engine step"):
                ts = time.perf_counter()
                st = engine.step()
                te = time.perf_counter()
            t_start[k], t_end[k] = ts, te
            admits[k] = st["admitted"]
            if st["admitted"]:
                n_admitted += st["admitted"]
                for s, meta in enumerate(engine._slot_meta):
                    if meta is not None and meta.completion.uid not in comp_of:
                        comp_of[meta.completion.uid] = meta.completion
                        slot_of[meta.completion.uid] = s
                # a request that ended in the step that admitted it left
                # no slot to read; its tokens still count
                for c in engine.completions[n_done:]:
                    comp_of.setdefault(c.uid, c)
            n_done = len(engine.completions)
        else:
            mark = T0 + warm if t_w0 is None else t_w0 + cell.seconds
            nxt = T0 + due[i] if i < n else mark
            time.sleep(max(0.0, min(nxt, mark) - time.perf_counter()))
    if cell.trace_dir:
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
    cell.compiles.active = False
    gc.callbacks.remove(on_gc)
    gc.unfreeze()
    window = t_w1 - t_w0
    setup_s = t_w0 - cell.t_process
    longest = sorted(((t_end[k] - t_start[k], k) for k in range(k0, k1)),
                     reverse=True)[:3]

    # ---- window statistics from the completions and the step clock
    ttft, itl, qwait = [], [], []
    tokens = 0
    prefills, contexts = [], {k: [] for k in range(k0, k1)}
    for uid, c in comp_of.items():
        a = c.admitted_step
        for j in range(len(c.tokens)):
            k = a + max(j - 1, 0)
            if not k0 <= k < k1:
                continue
            tokens += 1
            if j >= 1:
                contexts[k].append(c.prompt_len + j)
                prev = t_end[a + max(j - 2, 0)]
                itl.append(t_end[k] - prev)
        if k0 <= a < k1:
            prefills.append(c.prompt_len)
    for r in range(n):
        d = T0 + due[r]
        if not t_w0 <= d < t_w1:
            continue
        c = comp_of.get(r)
        if c is None:
            ttft.append(t_w1 - d)
            continue
        ttft.append(t_end[c.admitted_step] - d)
        qwait.append(max(0.0, t_start[c.admitted_step] - d))
    # attempted: requests due in the window, and (a backlog's) requests
    # served in it
    served = {uid for uid, c in comp_of.items()
              if c.admitted_step < k1 and c.admitted_step + len(c.tokens) > k0}
    attempted = len({r for r in range(n) if t_w0 <= T0 + due[r] < t_w1}
                    | served)
    print(f"window: {window!r} s, engine steps {k1 - k0}, tokens {tokens}, "
          f"requests due {attempted} (admitted {len(qwait)}), prefills "
          f"{len(prefills)}; queue at end {len(engine.queue)}; compiles in "
          f"window: {cell.compiles.count}; generator late p95 "
          f"{np.percentile(late, 95) if late else 0.0!r} s, max "
          f"{max(late) if late else 0.0!r} s", file=sys.stderr)
    print("longest engine steps in the window: " + ", ".join(
        f"{1e3 * d!r} ms (step {k - k0}, {admits[k]} admitted)"
        for d, k in longest), file=sys.stderr)
    in_window = [d for t, d in pauses if t_w0 <= t < t_w1]
    print(f"collector in the window: {len(in_window)} pauses, longest "
          f"{1e3 * max(in_window, default=0.0)!r} ms", file=sys.stderr)
    if ttft:
        print(f"ttft p50 {1e3 * np.percentile(ttft, 50)!r} ms; itl p50 "
              f"{1e3 * np.percentile(itl, 50) if itl else 0.0!r} ms",
              file=sys.stderr)
    mem = cell.memory_peak()
    queue_end = len(engine.queue)
    # a request that ended in the step that admitted it has no slot to
    # read, and is never sampled
    finished = [c for c in engine.completions
                if c.uid in slot_of and k0 <= c.finished_step < k1]
    del engine
    gc.collect()
    checks = check(cell, params, finished, slot_of, engine_seed)
    if keep is not None:
        keep["args"] = (params, finished, slot_of, engine_seed)
        keep["readings"] = checks
    e2e = {"setup_s": setup_s,
           "serve_tokens_per_s": tokens / window,
           "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95))
           if ttft else float("nan"),
           "itl_p95_ms": 1e3 * float(np.percentile(itl, 95))
           if itl else float("nan")}
    counts = {"window_s": window, "engine_steps": k1 - k0,
              "queue_at_end": queue_end, "due": len(ttft),
              "admitted": len(qwait),
              "queue_wait_s": qwait, "prefill_lens": prefills,
              "decode_contexts": [contexts[k] for k in range(k0, k1)]}
    return {"e2e": e2e, "counts": counts, "attempted": attempted,
            "failed": 0, "checks": checks, "memory_peak": mem}


def sample(finished, seed: int, n: int):
    """``n`` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda c: (len(c.tokens), c.uid))
    rest = sorted((c for c in finished if c is not longest),
                  key=lambda c: c.uid)
    pick = generate.rng(seed, "order", 7).permutation(len(rest))[:n - 1]
    return [longest] + [rest[j] for j in sorted(pick)]


def positions(c, slot: int, engine_seed: int, d: int):
    """Wire seed and payload offset of every input position of ``c``."""
    S, a = c.prompt_len, c.admitted_step
    T = S + len(c.tokens) - 1
    seeds = np.zeros(T, np.uint32)
    offs = np.zeros(T, np.uint32)
    seeds[:S] = (engine_seed + PREFILL_SEED * (a + 1)) & 0x7FFFFFFF
    offs[:S] = np.arange(S) * d
    for j in range(1, len(c.tokens)):
        k = a + j - 1
        seeds[S + j - 1] = (engine_seed ^ DECODE_SEED * (k + 1)) & 0x7FFFFFFF
        offs[S + j - 1] = slot * d
    return seeds, offs


def check(cell, params, finished, slot_of, engine_seed, control=False):
    """[(name, value)]: served tokens against the reference's logits; with
    ``control``, the tokens that the reference in bfloat16 puts first."""
    import jax
    import jax.numpy as jnp

    ref = cell.reference
    cfgj, tj = cell.config, cell.traffic
    rc = reference_config(cfgj, tj)
    T = tj["max_len"]
    d = cfgj["d_model"]

    @jax.jit
    def gaps(base, toks, seeds, offs, first, pos, served):
        W = weights.plain(base)
        lg = ref.serve_logits(rc, W, toks, seeds, offs, first)[pos]
        best = jnp.max(lg, axis=-1)
        if control:
            served = jnp.argmax(ref.serve_logits(
                rc, W, toks, seeds, offs, first, jnp.bfloat16)[pos], axis=-1)
        return best - jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]

    widest, count, total, moved = 0.0, 0, 0.0, 0
    for c in sample(finished, cell.seed, tj["check"]["requests"]):
        prompt = generate.prompt_tokens(cell.seed, c.uid, c.prompt_len,
                                        cfgj["vocab_size"])
        seq = np.concatenate([prompt, np.asarray(c.tokens[:-1], np.int32)])
        seeds, offs = positions(c, slot_of[c.uid], engine_seed, d)
        pad = T - len(seq)
        pos = np.arange(c.prompt_len - 1, len(seq))
        g = np.asarray(gaps(params, jnp.asarray(np.pad(seq, (0, pad))),
                            jnp.asarray(np.pad(seeds, (0, pad))),
                            jnp.asarray(np.pad(offs, (0, pad))),
                            jnp.int32(c.prompt_len),
                            jnp.asarray(np.pad(pos, (0, T - len(pos)))),
                            jnp.asarray(np.pad(np.asarray(c.tokens, np.int32),
                                               (0, T - len(pos))))))
        g = g[:len(pos)]
        widest = max(widest, float(g.max()))
        count += len(pos)
        total += float(g.sum())
        moved += int((g > 0).sum())
    print(f"reference: {count} served tokens compared; {moved} not the "
          f"reference's best", file=sys.stderr)
    if not count:
        return []
    return [("logit_gap", widest), ("logit_gap_mean", total / count)]
