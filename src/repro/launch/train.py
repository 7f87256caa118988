"""End-to-end training launcher.

Two modes:
* LM mode (``--arch`` from the zoo): SFL-GA split training of a reduced or
  full config on synthetic token streams, single-host (CPU) or production
  mesh. This is the (b) end-to-end driver: ``--preset 100m`` trains a
  ~100M-param model for a few hundred steps.
* CNN mode (``--arch paper-cnn``): the paper's own experiment via the
  federated simulator.

Both modes run the same protocol engine (core.protocol): ``--uplink-codec``
/ ``--downlink-codec`` put a lossy transport on the cut-layer boundary and
``--tau`` runs τ local steps per round; traffic is reported by the unified
``sysmodel.traffic`` accounting.

``--dynamic-cut`` runs the paper's headline feature — per-round cut
migration — in either mode: a comma list ("1,2,1") is cycled over
rounds/steps, and ``ddqn[:EPISODES]`` trains Algorithm 1 first (CNN mode
executes the policy against the live channel via core.closed_loop; LM
mode freezes the greedy rollout). Migration traffic (boundary layers
moving between client and server) is priced by
``sysmodel.traffic.migration_bits``.

``--peft lora`` (LM mode) federates LoRA adapters instead of full client
layers (DESIGN.md §17): the frozen base never crosses the wire, model
sync and cut migration ship only the adapter sliver — which is what
makes ``--bank host --dynamic-cut`` viable at bank scale.

``--cohort K --sampler S`` runs PARTIAL participation in either mode:
each round/step samples K of ``--clients`` devices from the bank
(core.cohort — uniform / ρ-weighted / latency-aware straggler-avoiding),
trains just those, and folds the results back with unbiased cohort
re-weighting. Server-side state is ONE copy regardless of N, so
``--clients 10000 --cohort 16`` costs the same per round as N=16
(benchmarks/fig11_scale.py).

Examples:
  python -m repro.launch.train --arch granite-8b --preset 100m --steps 300
  python -m repro.launch.train --arch paper-cnn --rounds 20 \
      --clients 256 --cohort 8 --sampler uniform
  python -m repro.launch.train --arch granite-8b --preset smoke --steps 2 \
      --uplink-codec int8 --downlink-codec int8 --tau 2
  python -m repro.launch.train --arch granite-8b --preset smoke --layers 3 \
      --steps 4 --dynamic-cut 1,2
  python -m repro.launch.train --arch paper-cnn --scheme sfl_ga --cut 2 --rounds 100
  python -m repro.launch.train --arch paper-cnn --rounds 40 --dynamic-cut ddqn:40
  python -m repro.launch.train --arch granite-8b --preset smoke --layers 3 \
      --steps 4 --peft lora --lora-rank 8 --cohort 4 --clients 16 \
      --bank host --dynamic-cut ddqn:4
"""
from __future__ import annotations

import argparse
import json
import os
import time
from contextlib import contextmanager

import numpy as np

from repro import obs
from repro.launch.compile_cache import enable_compile_cache


@contextmanager
def _maybe_profile(args, step: int):
    """``--profile N``: capture a ``jax.profiler`` trace of the one
    designated round/step (the trace of a single post-warmup step is
    what you can actually read; tracing a whole run is noise). A trace
    that was asked for and cannot be taken fails the run."""
    if args.profile is None or step != args.profile:
        yield
        return
    import jax

    out = os.path.join(args.metrics_dir or ".", "profile")
    jax.profiler.start_trace(out)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
    obs.log(f"profiler trace for step {step} -> {out}")


def train_lm(args) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.checkpoint import save_checkpoint
    from repro.configs import TrainConfig, get_config, reduced_config
    from repro.core import algorithms as alg
    from repro.data.synthetic import synthetic_token_batches
    from repro.models import lm
    from repro.optim import make_optimizer

    cfg = get_config(args.arch)
    if args.preset == "smoke":
        cfg = reduced_config(cfg)
    elif args.preset == "100m":
        # ~100M params in the same family
        cfg = reduced_config(cfg).with_overrides(
            name=cfg.name + "-100m", num_layers=4, d_model=512,
            num_heads=8 if cfg.num_heads else 0,
            num_kv_heads=4 if cfg.num_kv_heads else 0,
            d_ff=min(cfg.d_ff, 2048) if cfg.d_ff else 0,
            vocab_size=min(cfg.vocab_size, 32768), head_dim=64)
    if args.layers:
        cfg = cfg.with_overrides(num_layers=args.layers)
    from repro.core.protocol import round_seed
    from repro.core.split import client_adapter_numel, client_param_numel
    from repro.sysmodel.traffic import adapter_migration_bits, migration_bits

    peft = None
    if args.peft == "lora":
        from repro.configs.base import PeftSpec

        peft = PeftSpec(kind="lora", rank=args.lora_rank,
                        alpha=args.lora_alpha)

    n, b, S, tau = args.clients, args.batch, args.seq, args.tau
    K = args.cohort or n
    sampler = None
    if args.cohort:
        from repro.core.cohort import make_sampler
        from repro.core.protocol import scheme_spec

        sampler = make_sampler(args.sampler, n, K, seed=args.seed)
        spec = scheme_spec(args.scheme)
        obs.log(f"cohort: {K}/{n} clients per step ({args.sampler} sampler)")
    schedule = _parse_dynamic_cut(args, lm_mode=True)
    if isinstance(schedule, str):  # "ddqn[:EPISODES]" — train Algorithm 1
        schedule = _lm_ddqn_schedule(schedule, args, cfg, peft, n, b, S)
    # LM resume: the checkpoint pins the cut (and the schedule replays
    # the identical migrations from the absolute step index)
    done = 0
    if args.resume:
        from repro.checkpoint import load_checkpoint_meta
        rmeta = load_checkpoint_meta(args.resume)
        if str(rmeta.get("peft", "none")) != args.peft:
            raise SystemExit(f"--resume checkpoint was trained with "
                             f"--peft {rmeta.get('peft', 'none')}, "
                             f"run asked for --peft {args.peft}")
        done = int(rmeta["step"])
        cut0 = int(rmeta["cut"])
    else:
        cut0 = schedule(0) if schedule else args.cut
    tcfg = TrainConfig(model=cfg, algo=args.scheme, cut_layer=cut0,
                       compute_dtype="float32", param_dtype="float32",
                       lr=args.lr, remat=False, tau=tau,
                       uplink_codec=args.uplink_codec,
                       downlink_codec=args.downlink_codec,
                       peft=args.peft, lora_rank=args.lora_rank,
                       lora_alpha=args.lora_alpha, seed=args.seed)
    # one engine for the whole run: the launcher owns it (instead of
    # make_train_step's internal default) so the obs traffic ledger can
    # meter the exact transport the steps trace. float32 compute → the
    # raw wire is 32 bits/element, matching comm_bytes_per_round's
    # bytes_per_elem=4 below.
    from repro.core.protocol import ProtocolEngine

    rec = obs.get_recorder()
    engine = ProtocolEngine(args.scheme, args.uplink_codec,
                            args.downlink_codec, base_seed=args.seed,
                            adapter_sync=peft is not None)
    if rec.enabled:
        engine.attach_ledger(rec.ledger, raw_bits_per_elem=32.0,
                             label_bits_per_epoch=b * S * 32)
    plans = {cut0: lm.build_plan(cfg, cut0, peft=peft)}
    cut = cut0
    # the BANK holds all N per-client stacks; the jitted step only ever
    # sees the K gathered participants (server side is shared, O(1) in N)
    base_init = lm.init_lm(jax.random.key(args.seed), plans[cut0],
                           jnp.float32)
    if peft is None:
        params = alg.split_lm_params(base_init, n)
    else:
        # PEFT (DESIGN.md §17): client/server hold ONLY adapter slivers;
        # the frozen base rides under params["base"] and never trains
        loras = lm.init_lm_loras(
            jax.random.fold_in(jax.random.key(args.seed), 1),
            plans[cut0], jnp.float32)
        params = alg.split_lm_lora_params(base_init, loras, n)
        obs.log(f"peft: lora rank {args.lora_rank} alpha "
                f"{args.lora_alpha:g} — {client_adapter_numel(plans[cut0])}"
                f" trainable client params/client of "
                f"{client_param_numel(plans[cut0])} resident")
    opt = make_optimizer(args.optimizer, args.lr)
    opt_state = opt.init(alg.trainable_params(params))
    if args.resume:
        from repro.checkpoint import load_checkpoint

        state, _ = load_checkpoint(args.resume,
                                   {"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        obs.log(f"resumed from {args.resume} at step {done} (cut {cut0}); "
                f"--steps {args.steps} more to run")
    if args.bank == "sharded":
        raise SystemExit("--bank sharded is CNN-mode only; LM runs "
                         "shard the client bank via launch.shardings "
                         "on real meshes")
    if args.bank == "host":
        if schedule is not None and peft is None:
            raise SystemExit("--bank host cannot run --dynamic-cut with "
                             "--peft none: a full-parameter resplit would "
                             "round-trip the whole O(N) bank through the "
                             "device every migration. Run --peft lora "
                             "(DESIGN.md §17): only the adapter sliver "
                             "migrates, so the host bank re-splits in O(N·"
                             "adapter) host work with zero model wire cost")
        if sampler is None:
            raise SystemExit("--bank host needs --cohort in LM mode (the "
                             "identity cohort re-gathers the whole bank "
                             "every step)")
    steps_by_cut = {cut0: jax.jit(alg.make_train_step(plans[cut0], tcfg, opt,
                                                      K, engine=engine))}
    if args.async_mode:
        if schedule is not None:
            raise SystemExit("--async cannot run --dynamic-cut in LM mode: "
                             "in-flight payload shapes are cut-static")
        if args.resume:
            raise SystemExit("--async LM mode does not support --resume "
                             "(the event schedule is not checkpointed; "
                             "resume the barrier loop instead)")
        if args.bank != "device":
            raise SystemExit("--async LM mode needs --bank device")
        if engine.spec.client_aggregate:
            raise SystemExit("--async LM mode covers sfl_ga/psl (schemes "
                             "without round-end client aggregation)")
        if args.optimizer != "sgd":
            raise SystemExit("--async LM mode needs --optimizer sgd: "
                             "staleness-discounting per-client optimizer "
                             "moments is not defined")
        gen_fn = jax.jit(alg.make_gen_step(plans[cut0], tcfg, opt, K,
                                           engine=engine))
        return _run_lm_async(args, cfg, plans[cut0], tcfg, engine, params,
                             opt_state, steps_by_cut[cut0], gen_fn, rec,
                             n, K, b, S, tau)
    # the O(N) client-side stacks (params + any optimizer moments) live in
    # ClientBanks (core.bank); each step gathers only the cohort's rows.
    # --bank host keeps them in host memory and double-buffers the copies
    # behind the jitted step
    from repro.core.bank import ClientBank

    pbank = ClientBank(params["client"], n_clients=n, stacked=True,
                       backend=args.bank)
    params = dict(params, client=None)  # the bank owns the client side
    obanks = {}
    for mk in ("m", "v", "mu"):
        if mk in opt_state:
            obanks[mk] = ClientBank(opt_state[mk]["client"], n_clients=n,
                                    stacked=True, backend=args.bank)
            opt_state[mk] = dict(opt_state[mk], client=None)
    if args.bank == "host":
        off = pbank.nbytes + sum(b.nbytes for b in obanks.values())
        obs.log(f"client bank: host backend ({off / 1e6:.2f} MB params"
                f"{' + moments' if obanks else ''} off-device)")

    def per_client_numel(client_tree):
        leaves = jax.tree.leaves(client_tree)
        return sum(int(np.prod(l.shape)) for l in leaves) // n

    it = synthetic_token_batches(cfg.vocab_size, K * b * tau, S, seed=args.seed)
    for _ in range(done):
        next(it)  # resume: continue the uninterrupted batch sequence
    shape = (K, b, S) if tau == 1 else (K, tau, b, S)
    losses = []
    mig_total_bits = 0
    n_migrations = 0
    t0 = time.time()
    for i in range(done, done + args.steps):
        if rec.enabled:
            rec.set_round(i)
        if schedule is not None:
            v = schedule(i)
            if v != cut:
                # migrate the boundary layers (and any optimizer moments)
                # to the new cut; migration traffic is model parameters at
                # the raw fp32 wire (sysmodel.traffic.migration_bits) —
                # under PEFT only the adapter sliver moves, the frozen
                # base is a pure relayout (resplit_base_params)
                if v not in plans:
                    plans[v] = lm.build_plan(cfg, v, peft=peft)
                    steps_by_cut[v] = jax.jit(
                        alg.make_train_step(plans[v], tcfg, opt, K,
                                            engine=engine))
                # the whole BANK migrates (resplit is N-agnostic); wire
                # cost is paid by the K participants of the step. The
                # banks' contents are swapped — on the host backend (LoRA-
                # only, see the guard above) replace() also invalidates
                # any staged prefetch, so the next gather re-slices. The
                # trees are read after a flush, so every pending host
                # scatter has landed
                for bk in (pbank, *obanks.values()):
                    bk.flush()
                fp = dict(params, client=pbank.tree)
                fo = dict(opt_state)
                for mk, bk in obanks.items():
                    fo[mk] = dict(opt_state[mk], client=bk.tree)
                per_old = per_client_numel(fp["client"])
                fp = alg.resplit_lm_params(fp, plans[cut], plans[v])
                fo = alg.resplit_opt_state(fo, plans[cut], plans[v])
                per_new = per_client_numel(fp["client"])
                pbank.replace(fp["client"])
                params = dict(fp, client=None)
                opt_state = fo
                for mk, bk in obanks.items():
                    bk.replace(fo[mk]["client"])
                    opt_state[mk] = dict(fo[mk], client=None)
                if peft is None:
                    mb = migration_bits(client_param_numel(plans[cut]),
                                        client_param_numel(plans[v]),
                                        n_clients=K, raw_bits_per_elem=32)
                else:
                    mb = adapter_migration_bits(
                        client_adapter_numel(plans[cut]),
                        client_adapter_numel(plans[v]),
                        n_clients=K, raw_bits_per_elem=32)
                mig_total_bits += mb["total_bits"]
                n_migrations += 1
                if rec.enabled:
                    # measured from the bank tensors that actually moved
                    # sides, vs the plan-φ-delta pricing
                    payload = abs(per_new - per_old) * 32 * K
                    rec.event(
                        "migration", name="resplit", scheme=args.scheme,
                        cut=v, cut_from=cut, participants=K,
                        peft=args.peft,
                        measured={
                            "up_bits": payload if per_new < per_old else 0,
                            "down_bits": payload if per_new > per_old else 0,
                            "total_bits": payload},
                        modeled=mb)
                obs.log(f"step {i}: cut {cut} -> {v} "
                        f"(migrated {mb['total_bits']/8e6:.2f} MB)")
                cut = v
        toks, labels = next(it)
        batch = {"tokens": jnp.asarray(toks.reshape(shape)),
                 "labels": jnp.asarray(labels.reshape(shape)),
                 "seed": round_seed(args.seed, i)}
        with _maybe_profile(args, i), rec.span("step", cut=cut):
            # gather the step-i cohort (params + optimizer moments), train
            # with unbiased cohort weights, scatter back (sfl broadcasts its
            # new global client model). Full participation is the identity
            # cohort: idx=None gathers the whole bank and scatters it back
            # wholesale, the step itself having aggregated every row
            idx, broadcast, nxt = None, False, None
            if sampler is not None:
                idx, w = sampler.cohort(i)
                batch = dict(batch, rho=jnp.asarray(w))
                broadcast = spec.client_aggregate
            cp = dict(params, client=pbank.gather(idx, t=i))
            cop = dict(opt_state)
            for mk, bk in obanks.items():
                cop[mk] = dict(opt_state[mk], client=bk.gather(idx, t=i))
            if pbank.prefetch_enabled:
                # disjoint next cohort: stage its slice while this step
                # trains (else wait until the scatter enqueues).
                # Aggregating schemes (sfl) BROADCAST-scatter — every row
                # rewrites, so disjointness proves nothing and any early
                # stage would be invalidated anyway; always prefetch after
                # the scatter there.
                nxt, _ = sampler.peek(i + 1)
                if not broadcast and np.intersect1d(idx, nxt).size == 0:
                    pbank.prefetch(i + 1, nxt)
                    for bk in obanks.values():
                        bk.prefetch(i + 1, nxt)
                    nxt = None
            # a PEFT step returns only the trainable tree: params keeps
            # the base
            cp, cop, m = steps_by_cut[cut](cp, cop, batch)
            pbank.scatter(idx, cp["client"], broadcast=broadcast)
            params = dict(params, server=cp["server"])
            opt_state = dict(cop)
            for mk, bk in obanks.items():
                # moments scatter per-row even under sfl: each client
                # keeps its OWN moment history
                bk.scatter(idx, cop[mk]["client"])
                opt_state[mk] = dict(cop[mk], client=None)
            if nxt is not None:
                pbank.prefetch(i + 1, nxt)
                for bk in obanks.values():
                    bk.prefetch(i + 1, nxt)
            losses.append(float(m["loss"]))  # sync point inside the span
        if rec.enabled:
            jax.effects_barrier()  # drain the step's ledger callbacks
            rec.event(
                "traffic", name="step_traffic", scheme=args.scheme, cut=cut,
                tau=tau, participants=K, uplink_codec=args.uplink_codec,
                downlink_codec=args.downlink_codec,
                measured=rec.ledger.snapshot_and_reset(),
                modeled=alg.comm_breakdown_per_round(
                    cfg, plans[cut], args.scheme, K, b, S, tau=tau,
                    bytes_per_elem=4, uplink_codec=args.uplink_codec,
                    downlink_codec=args.downlink_codec))
            rec.event("round", name="lm_step", loss=losses[-1], cut=cut,
                      participants=K)
        if (i + 1) % args.log_every == 0:
            obs.log(f"step {i+1}/{done+args.steps} loss {losses[-1]:.4f} "
                    f"({(time.time()-t0)/(i+1-done):.2f} s/step)")
    # close() drains the pipeline AND releases the worker threads; the
    # banks stay readable for the stats/checkpoint reads below
    pbank.close()
    for bk in obanks.values():
        bk.close()
    st = pbank.stats()
    if args.bank == "host":
        obs.log(f"bank[host]: peak device client-state "
                f"{st['device_bytes_peak'] / 1e6:.2f} MB of "
                f"{st['bank_bytes'] / 1e6:.2f} MB bank; prefetch "
                f"{st['prefetch_hits']} hits / {st['prefetch_misses']} "
                f"misses")
    if rec.enabled:
        rec.event("bank", name="bank", **st)
    if args.checkpoint:
        # payload carries params AND optimizer state with full-bank
        # client trees (residency-agnostic: the host banks' numpy rows
        # serialize identically to device arrays), so --resume is
        # bit-exact under any --bank backend
        pl = dict(params, client=pbank.tree)
        ol = dict(opt_state)
        for mk, bk in obanks.items():
            ol[mk] = dict(opt_state[mk], client=bk.tree)
        save_checkpoint(args.checkpoint, {"params": pl, "opt": ol},
                        {"arch": cfg.name, "algo": args.scheme, "cut": cut,
                         "step": done + args.steps, "peft": args.peft,
                         "lora_rank": args.lora_rank,
                         "lora_alpha": args.lora_alpha,
                         "final_loss": losses[-1],
                         "bank_backend": args.bank})
        obs.log(f"checkpoint -> {args.checkpoint} (step {done + args.steps})")
    # unified per-round traffic (sysmodel.traffic via the LLM adapter)
    # priced for the K participants of a step; this run computes in
    # float32, so the raw wire is 4 bytes/element
    cb = alg.comm_bytes_per_round(
        cfg, plans[cut], args.scheme, K, b, S, tau=tau, bytes_per_elem=4,
        uplink_codec=args.uplink_codec, downlink_codec=args.downlink_codec)
    msg = (f"final loss {losses[-1]:.4f} (start {losses[0]:.4f}); "
           f"comm/round {cb['total_bytes']/1e6:.2f} MB "
           f"(up {cb['up_bytes']/1e6:.2f} / down {cb['down_bytes']/1e6:.2f}, "
           f"codecs {args.uplink_codec}/{args.downlink_codec})")
    if schedule is not None:
        msg += (f"; {n_migrations} cut migrations, "
                f"{mig_total_bits/8e6:.2f} MB migrated")
    obs.log(msg)
    return {"first_loss": losses[0], "final_loss": losses[-1], "comm": cb,
            "migration_bits": mig_total_bits, "n_migrations": n_migrations}


class _LMAsyncExecutor:
    """``core.async_engine`` executor over the LM train loop.

    Dispatch runs ``algorithms.make_gen_step`` against the live models.
    The LM step's joint loss yields ONE server gradient per generation
    (per-client server deltas don't exist — the τ local steps compound
    the joint update), so server merges are GENERATION-granular: a
    generation's delta folds in, staleness-discounted, at the merge
    where its last member lands. Client rows (sfl_ga / psl personalize
    client sides) scatter back per job as they complete."""

    def __init__(self, state, gen_fn, sync_step, data_fn, engine,
                 modeled_fn, rec):
        from functools import partial

        import jax

        from repro.core.protocol import merge_async

        self.state = state  # {"params", "opt_state"} — launcher-shared
        self.gen_fn = gen_fn
        self.sync_step = sync_step
        self.data_fn = data_fn
        self.engine = engine
        self.modeled_fn = modeled_fn
        self.rec = rec
        self._left = {}      # gen -> members not yet merged
        self._dispatch = []  # generation sizes since last merge
        self._merge_fns = {}
        self._mk_merge = lambda lam: jax.jit(partial(merge_async, lam=lam))

    def run_sync(self, d, idx, w):
        import jax.numpy as jnp

        from repro.core import algorithms as alg

        batch = self.data_fn(d, idx)
        cp = alg.gather_cohort(self.state["params"], idx)
        cp, self.state["opt_state"], m = self.sync_step(
            cp, self.state["opt_state"], dict(batch, rho=jnp.asarray(w)))
        self.state["params"] = alg.scatter_cohort(
            self.state["params"], cp, idx)
        return {"loss": float(m["loss"])}

    def run_generation(self, d, idx, w):
        import jax.numpy as jnp

        from repro.core import algorithms as alg

        idx = np.asarray(idx, np.int64)
        batch = self.data_fn(d, idx)
        cp = alg.gather_cohort(self.state["params"], idx)
        out, self.state["opt_state"] = self.gen_fn(
            cp, self.state["opt_state"], dict(batch, rho=jnp.asarray(w)))
        self._left[d] = int(idx.size)
        self._dispatch.append(int(idx.size))
        return {"idx": idx, "loss": out["loss"],
                "server_delta": out["server_delta"],
                "client": out["client"]}

    def apply_merge(self, items, taus, lam, merge_idx):
        import jax
        import jax.numpy as jnp

        from repro.core.bank import scatter_rows

        params = self.state["params"]
        idx = jnp.asarray(
            np.asarray([it["client"] for it in items], np.int64))
        rows = [jax.tree.map(lambda x, p=it["pos"]: x[p],
                             it["payload"]["client"]) for it in items]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
        client = scatter_rows(params["client"], stacked, idx)
        # generation-granular server merge: fold a generation's delta in
        # when its LAST member completes, at that merge's staleness
        done = []
        for it, t in zip(items, taus):
            g = it["gen"]
            self._left[g] -= 1
            if self._left[g] == 0:
                done.append((it["payload"]["server_delta"], float(t)))
                del self._left[g]
        server = params["server"]
        if done:
            fn = self._merge_fns.get(lam)
            if fn is None:
                fn = self._merge_fns[lam] = self._mk_merge(lam)
            deltas = jax.tree.map(lambda *xs: jnp.stack(xs),
                                  *[d for d, _ in done])
            server = fn(server, deltas,
                        jnp.ones((len(done),), jnp.float32),
                        jnp.asarray([t for _, t in done], jnp.float32))
        self.state["params"] = dict(params, client=client, server=server)
        loss = float(np.mean([float(it["payload"]["loss"])
                              for it in items]))
        out = {"loss": loss, "merged_gens": len(done)}
        if self.rec.enabled:
            import jax as _jax

            _jax.effects_barrier()
            self.rec.event(
                "traffic", name="async_traffic",
                scheme=self.engine.spec.name, participants=len(items),
                dispatched=list(self._dispatch),
                measured=self.rec.ledger.snapshot_and_reset(),
                modeled=self.modeled_fn(self._dispatch, len(items)))
        self._dispatch = []
        return out


def _run_lm_async(args, cfg, plan, tcfg, engine, params, opt_state,
                  sync_step, gen_fn, rec, n, K, b, S, tau) -> dict:
    """LM mode under ``--async``: the event-driven engine replaces the
    barrier step loop; ``--steps`` counts merges."""
    import jax.numpy as jnp

    from repro.core import algorithms as alg
    from repro.core.async_engine import AsyncRoundEngine
    from repro.core.cohort import AdmissionSampler, make_sampler
    from repro.core.protocol import round_seed
    from repro.data.synthetic import synthetic_token_batches
    from repro.sysmodel.latency import completion_time_fn

    buffer = args.buffer or K
    base = make_sampler(args.sampler if args.cohort else "uniform", n, K,
                        seed=args.seed)
    admission = AdmissionSampler(base, buffer)
    completion = completion_time_fn(
        n, seed=args.seed, straggler_factor=args.straggler, batch=b)

    def data_fn(d, idx):
        g = len(idx)
        seed = int(round_seed(args.seed, d))
        it = synthetic_token_batches(cfg.vocab_size, g * b * tau, S,
                                     seed=seed)
        toks, labels = next(it)  # pure in d: fresh stream per generation
        shape = (g, b, S) if tau == 1 else (g, tau, b, S)
        return {"tokens": jnp.asarray(toks.reshape(shape)),
                "labels": jnp.asarray(labels.reshape(shape)),
                "seed": round_seed(args.seed, d)}

    def modeled_fn(dispatch_sizes, merged):
        from repro.obs.ledger import LEDGER_CATEGORIES

        acc = {c: 0 for c in LEDGER_CATEGORIES}
        for g in dispatch_sizes:
            bd = alg.comm_breakdown_per_round(
                cfg, plan, args.scheme, g, b, S, tau=tau, bytes_per_elem=4,
                uplink_codec=args.uplink_codec,
                downlink_codec=args.downlink_codec)
            for c in acc:
                acc[c] += bd[c]
        return acc

    state = {"params": params, "opt_state": opt_state}
    ex = _LMAsyncExecutor(state, gen_fn, sync_step, data_fn, engine,
                          modeled_fn, rec)
    eng = AsyncRoundEngine(ex, admission, completion, buffer=buffer,
                           lam=args.staleness_lam)
    obs.log(f"async engine: buffer B={buffer} of K={K} in flight, "
            f"straggler x{args.straggler:g}, lam={args.staleness_lam:g}")
    losses, t0 = [], time.time()
    for i in range(args.steps):
        if rec.enabled:
            rec.set_round(i)
        with rec.span("step", cut=tcfg.cut_layer):
            m = eng.step()
        losses.append(float(m["loss"]))
        if rec.enabled:
            rec.event("round", name="lm_step", loss=losses[-1],
                      cut=tcfg.cut_layer, participants=m["merged"])
        if (i + 1) % args.log_every == 0:
            obs.log(f"merge {i+1}/{args.steps} loss {losses[-1]:.4f} "
                    f"clock {m['clock']:.1f}s stale "
                    f"{m['staleness_mean']:.1f} "
                    f"({(time.time()-t0)/(i+1):.2f} s/step)")
    eng.drain()
    st = eng.stats()
    if args.checkpoint:
        from repro.checkpoint import save_checkpoint

        save_checkpoint(args.checkpoint,
                        {"params": state["params"],
                         "opt": state["opt_state"]},
                        {"arch": cfg.name, "algo": args.scheme,
                         "cut": tcfg.cut_layer, "step": args.steps,
                         "peft": args.peft, "lora_rank": args.lora_rank,
                         "lora_alpha": args.lora_alpha,
                         "final_loss": losses[-1], "bank_backend": "device"})
        obs.log(f"checkpoint -> {args.checkpoint}")
    cb = alg.comm_bytes_per_round(
        cfg, plan, args.scheme, K, b, S, tau=tau, bytes_per_elem=4,
        uplink_codec=args.uplink_codec, downlink_codec=args.downlink_codec)
    obs.log(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f}); "
            f"virtual clock {st['clock']:.1f}s over {st['merges']} merges "
            f"({st['dispatches']} dispatches)")
    return {"first_loss": losses[0], "final_loss": losses[-1], "comm": cb,
            "async": st, "migration_bits": 0, "n_migrations": 0}


def _parse_dynamic_cut(args, lm_mode: bool):
    """``--dynamic-cut`` → CutSchedule (or None). Comma list ("1,2,1") in
    both modes; ``ddqn[:EPISODES]`` is resolved by the caller, which owns
    the env (CNN: the live closed loop; LM: a frozen greedy rollout)."""
    spec = args.dynamic_cut
    if not spec:
        return None
    from repro.core.closed_loop import CutSchedule

    if spec.startswith("ddqn"):
        return spec  # the mode-specific caller trains the agent
    return CutSchedule.from_sequence(
        [int(v) for v in spec.split(",")], name=f"sequence[{spec}]")


def _lm_ddqn_schedule(spec: str, args, cfg, peft, n: int, b: int, S: int):
    """LM ``--dynamic-cut ddqn[:EPISODES]``: train Algorithm 1 on the LM's
    φ(v)/X(v) MDP — with cut-migration pricing, adapter-cost under PEFT —
    then FREEZE the greedy rollout as a cycled schedule. Unlike the CNN
    closed loop the policy is not queried live per step: a frozen
    sequence is deterministic in the step index, which is what makes
    ``--resume`` replay the identical migrations."""
    from repro.ccc.env import CuttingPointEnv, lm_env_config
    from repro.ccc.strategy import run_algorithm1

    if cfg.num_layers < 2:
        raise SystemExit(f"--dynamic-cut ddqn needs >= 2 layers to have a "
                         f"cut to move ({cfg.name} has {cfg.num_layers}; "
                         f"try --layers 3)")
    episodes = int(spec.split(":")[1]) if ":" in spec else 30
    ecfg = lm_env_config(cfg, seq=S, peft=peft, n_clients=n, batch=b,
                         seed=args.seed, cohort=args.cohort)
    mig = "adapter-priced (lora)" if peft is not None else "full-φ-priced"
    obs.log(f"training Algorithm 1 policy on the LM MDP ({episodes} "
            f"episodes, {len(ecfg.phis)} cuts, migration {mig})...")
    res = run_algorithm1(CuttingPointEnv(ecfg), episodes=episodes)
    sched = res.cut_schedule()  # frozen greedy rollout, cycled
    obs.log(f"ddqn schedule: {res.greedy_policy}")
    return sched


def train_cnn(args) -> dict:
    if args.peft != "none":
        raise SystemExit("--peft is LM-mode only (the paper CNN trains "
                         "full parameters)")
    from repro.configs.paper_cnn import LIGHT_CONFIG
    from repro.core.simulator import FedSimulator, SimConfig
    from repro.data import iid_partition, make_image_dataset
    from repro.data.federated import (replacement_fraction, rho_weights,
                                      round_batches)

    ds = make_image_dataset(args.dataset, n=args.n_samples, seed=args.seed)
    train, test = ds.split(0.9)
    if args.clients > len(train.x):
        # more clients than samples: iid_partition would hand out EMPTY
        # partitions (and materialize O(N) index arrays at bank scale);
        # the cyclic view shares samples across clients instead — the
        # million-client regime only ever touches the round's K slices
        from repro.data.federated import CyclicPartition

        parts = CyclicPartition(len(train.x), args.clients)
        rho = None  # equal part sizes -> uniform ρ without an O(N) list
        obs.log(f"data: cyclic partition view ({args.clients} clients over "
                f"{len(train.x)} samples, {parts.part_size}/client)")
    else:
        parts = iid_partition(len(train.x), args.clients, seed=args.seed)
        rho = rho_weights(parts)
    sim = FedSimulator(LIGHT_CONFIG,
                       SimConfig(scheme=args.scheme, cut=args.cut,
                                 n_clients=args.clients, batch=args.batch,
                                 tau=args.tau, lr=args.lr,
                                 uplink_codec=args.uplink_codec,
                                 downlink_codec=args.downlink_codec,
                                 cohort=args.cohort,
                                 sampler=args.sampler if args.cohort
                                 else "full",
                                 cohort_seed=args.seed,
                                 bank=args.bank),
                       rho=rho, seed=args.seed)
    if args.bank != "device":
        obs.log(f"client bank: {args.bank} backend "
                f"({sim.bank.nbytes / 1e6:.2f} MB off-device)")
    if args.cohort:
        obs.log(f"cohort: {sim.n_participants}/{args.clients} clients per "
                f"round ({sim.sampler.kind} sampler)")
    rf = replacement_fraction(parts, args.batch)
    if rf:
        obs.log(f"note: {rf:.0%} of client partitions are smaller than the "
                f"batch ({args.batch}); their draws sample with replacement")
    done_rounds = 0
    if args.resume and not args.async_mode:
        meta = sim.restore(args.resume)
        done_rounds = sim._t
        obs.log(f"resumed from {args.resume} at round {sim._t} "
                f"(cut {sim.cut}); --rounds {args.rounds} more to run")
    schedule = _parse_dynamic_cut(args, lm_mode=False)
    if schedule is not None:
        result = _train_cnn_closed_loop(args, sim, schedule, train, test,
                                        parts, skip_batches=done_rounds)
    elif args.async_mode:
        from repro.core.protocol import round_seed

        def data_fn(d, idx):
            # pure in d (unlike the barrier loop's sequential rng): the
            # event schedule interleaves generations, and resume must
            # replay generation d's exact batches without a fast-forward
            rng_d = np.random.RandomState(
                int(round_seed(args.seed, d)) % (2**31 - 1))
            return round_batches(train, parts, args.batch, args.tau, rng_d,
                                 idx=np.asarray(idx))

        eng = sim.async_engine(data_fn, buffer=args.buffer,
                               lam=args.staleness_lam,
                               straggler_factor=args.straggler)
        obs.log(f"async engine: buffer B={eng.buffer} of "
                f"K={sim.n_participants} in flight, straggler "
                f"x{args.straggler:g}, lam={args.staleness_lam:g}")
        if args.resume:
            eng.restore(args.resume)
            obs.log(f"resumed async schedule from {args.resume} at merge "
                    f"{eng.merge_idx} (clock {eng.clock:.1f}s, "
                    f"{eng.queue_depth} in flight)")
        for r in range(args.rounds):
            with _maybe_profile(args, r):
                m = eng.step()
            if (r + 1) % args.log_every == 0:
                acc = sim.evaluate(test.x, test.y)
                obs.log(f"merge {r+1}/{args.rounds} loss {m['loss']:.4f} "
                        f"acc {acc:.3f} clock {m['clock']:.1f}s queue "
                        f"{m['queue_depth']} stale {m['staleness_mean']:.1f}")
        if args.checkpoint:
            # keep the in-flight queue: the checkpoint IS the schedule
            # state, and resume replays the identical merge order
            eng.save(args.checkpoint, {"scheme_args": args.scheme})
            obs.log(f"checkpoint -> {args.checkpoint} "
                    f"(merge {eng.merge_idx}, {eng.queue_depth} in flight)")
        else:
            eng.drain()
        st = eng.stats()
        acc = sim.evaluate(test.x, test.y)
        cb = sim.comm_bytes_per_round()
        obs.log(f"final acc {acc:.3f}; virtual clock {st['clock']:.1f}s "
                f"over {st['merges']} merges ({st['dispatches']} "
                f"dispatches, {st['sync_steps']} degenerate-sync); "
                f"comm/round {cb['total_bytes']/1e6:.3f} MB ({args.scheme})")
        result = {"accuracy": acc, "replacement_fraction": rf,
                  "async": st, **cb}
    else:
        rng = np.random.RandomState(args.seed)
        for t in range(done_rounds):
            # fast-forward the data stream past already-trained rounds so
            # a resumed run continues the uninterrupted batch sequence
            # (cohorts are pure in t, so the replay hits the same draws)
            idx, _ = sim.cohort_for_round(t)
            round_batches(train, parts, args.batch, args.tau, rng, idx=idx)
        for r in range(args.rounds):
            # τ DISTINCT local-epoch batches per participating client
            # (repeating one batch τ times would just be a τ-scaled
            # step, not τ epochs); O(K) data per round, not O(N)
            idx, _ = sim.cohort_for_round(sim._t)
            xs, ys = round_batches(train, parts, args.batch, args.tau, rng,
                                   idx=idx)
            with _maybe_profile(args, r):
                m = sim.run_round(xs, ys)
            if (r + 1) % args.log_every == 0:
                acc = sim.evaluate(test.x, test.y)
                obs.log(f"round {r+1}/{args.rounds} loss {m['loss']:.4f} "
                        f"acc {acc:.3f} drift {m['client_drift']:.2e}")
        acc = sim.evaluate(test.x, test.y)
        cb = sim.comm_bytes_per_round()
        obs.log(f"final acc {acc:.3f}; comm/round "
                f"{cb['total_bytes']/1e6:.3f} MB ({args.scheme}, "
                f"{sim.n_participants} participants)")
        result = {"accuracy": acc, "replacement_fraction": rf, **cb}
    if args.bank != "device":
        st = sim.bank.stats()
        obs.log(f"bank[{st['backend']}]: peak device client-state "
                f"{st['device_bytes_peak'] / 1e6:.2f} MB of "
                f"{st['bank_bytes'] / 1e6:.2f} MB bank; prefetch "
                f"{st['prefetch_hits']} hits / {st['prefetch_misses']} "
                f"misses")
        result["bank"] = st
    if args.checkpoint and not (args.async_mode and schedule is None):
        # async runs already checkpointed through the engine above (the
        # schedule state rides along with the model state)
        sim.save(args.checkpoint, {"scheme_args": args.scheme})
        obs.log(f"checkpoint -> {args.checkpoint} (round {sim._t})")
    return result


def _train_cnn_closed_loop(args, sim, schedule, train, test, parts,
                           skip_batches: int = 0) -> dict:
    """CNN mode with ``--dynamic-cut``: run the closed loop (live cut
    migration + wall-clock from the P2.1-solved allocation)."""
    from repro.ccc.env import CuttingPointEnv, cnn_env_config
    from repro.core.closed_loop import run_closed_loop

    # env cohort matches the simulator's: the DDQN observation and the
    # P2.1 bandwidth split cover the K participants, not the N-bank
    env = CuttingPointEnv(cnn_env_config(
        n_clients=args.clients, batch=args.batch, seed=args.seed,
        cohort=args.cohort, async_obs=args.async_mode))
    if isinstance(schedule, str):  # "ddqn[:EPISODES]"
        from repro.ccc.strategy import run_algorithm1

        episodes = int(schedule.split(":")[1]) if ":" in schedule else 60
        obs.log(f"training Algorithm 1 policy ({episodes} episodes)...")
        res = run_algorithm1(CuttingPointEnv(cnn_env_config(
            n_clients=args.clients, batch=args.batch, seed=args.seed,
            cohort=args.cohort, async_obs=args.async_mode)),
            episodes=episodes)
        schedule = res.cut_schedule(env)
    eng = None
    if args.async_mode:
        if args.resume:
            raise SystemExit("--async --dynamic-cut does not support "
                             "--resume (checkpoint the fixed-cut async "
                             "loop instead)")
        from repro.core.protocol import round_seed
        from repro.data.federated import round_batches

        def data_fn(d, idx):
            rng_d = np.random.RandomState(
                int(round_seed(args.seed, d)) % (2**31 - 1))
            return round_batches(train, parts, args.batch, args.tau, rng_d,
                                 idx=np.asarray(idx))

        eng = sim.async_engine(data_fn, buffer=args.buffer,
                               lam=args.staleness_lam,
                               straggler_factor=args.straggler)
        obs.log(f"async closed loop: buffer B={eng.buffer} of "
                f"K={sim.n_participants}, straggler x{args.straggler:g}; "
                f"policy sees queue depth + staleness "
                f"(state_dim {env.state_dim})")
    r = run_closed_loop(sim, env, schedule, train, test, parts,
                        rounds=args.rounds, eval_every=args.log_every,
                        batch_seed=args.seed, skip_batches=skip_batches,
                        log_every=args.log_every, async_engine=eng)
    obs.log(f"final acc {r.final_acc:.3f}; wall-clock {r.total_latency_s:.2f}s "
            f"({r.n_migrations} migrations, "
            f"{r.migration_bits_total/8e6:.2f} MB migrated); cuts {r.cuts}")
    return {"accuracy": r.final_acc, "wall_clock_s": r.total_latency_s,
            "cuts": r.cuts, "n_migrations": r.n_migrations,
            "migration_bits": r.migration_bits_total,
            "total_bits": r.total_bits}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--scheme", default="sfl_ga",
                   choices=["sfl_ga", "sfl", "psl", "fl"])
    p.add_argument("--preset", default="smoke", choices=["smoke", "100m", "full"])
    p.add_argument("--cut", type=int, default=1)
    p.add_argument("--clients", type=int, default=4)
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--rounds", type=int, default=50)
    p.add_argument("--tau", type=int, default=1,
                   help="local steps per round (both LM and CNN modes)")
    p.add_argument("--cohort", type=int, default=None,
                   help="partial participation: K clients sampled per round "
                        "out of --clients (both modes; default: everyone)")
    p.add_argument("--sampler", default="uniform",
                   choices=["full", "uniform", "rho", "latency"],
                   help="cohort sampler (core.cohort) when --cohort is set: "
                        "uniform (unbiased HT weights), rho (ρ-proportional "
                        "with replacement), latency (straggler-avoiding)")
    p.add_argument("--async", dest="async_mode", action="store_true",
                   help="event-driven buffered-async rounds (DESIGN.md §16): "
                        "drop the global barrier; merge the --buffer "
                        "earliest completions per step with staleness-"
                        "discounted weights (both modes; --rounds/--steps "
                        "count merges)")
    p.add_argument("--buffer", type=int, default=None, metavar="B",
                   help="async merge buffer B <= K (default K: with a "
                        "zero-spread completion draw this IS the sync loop)")
    p.add_argument("--straggler", type=float, default=4.0,
                   help="async completion-time heterogeneity: slowest/fastest "
                        "client speed ratio in sysmodel.latency draws")
    p.add_argument("--staleness-lam", type=float, default=0.5, metavar="LAM",
                   help="staleness discount exponent: deltas weigh "
                        "(1+tau)^-LAM after tau merges in flight")
    p.add_argument("--dynamic-cut", default=None,
                   help="per-round cut schedule: comma list '1,2,1' (cycled) "
                        "or 'ddqn[:EPISODES]' (train Algorithm 1 first; CNN "
                        "mode executes the live policy via core.closed_loop, "
                        "LM mode freezes the greedy rollout)")
    p.add_argument("--peft", default="none", choices=["none", "lora"],
                   help="LM mode: federate LoRA adapters instead of full "
                        "client layers (DESIGN.md §17) — the frozen base "
                        "never crosses the wire, model sync and cut "
                        "migration ship only the adapter sliver")
    p.add_argument("--lora-rank", type=int, default=8,
                   help="LoRA rank r per targeted projection (--peft lora)")
    p.add_argument("--lora-alpha", type=float, default=16.0,
                   help="LoRA scale numerator: adapters apply at alpha/r")
    p.add_argument("--layers", type=int, default=None,
                   help="override num_layers after the preset (e.g. give the "
                        "smoke preset 3 layers so --dynamic-cut 1,2 has room)")
    p.add_argument("--resume", default=None,
                   help="resume a checkpoint: CNN mode restores the "
                        "FedSimulator (params, round counter, cut); LM mode "
                        "restores params + optimizer state and fast-forwards "
                        "the data stream (bit-exact continuation)")
    p.add_argument("--bank", default="device",
                   choices=["device", "host", "sharded"],
                   help="client-bank residency (core.bank): device (stacked "
                        "pytree, the default), host (bank in host memory, "
                        "O(K) device bytes + prefetch; LM mode needs "
                        "--cohort and a static cut), sharded (bank over a "
                        "device mesh; CNN mode)")
    p.add_argument("--uplink-codec", default="fp32",
                   help="cut-layer uplink codec: fp32|bf16|fp8|int8|int4|topkP")
    p.add_argument("--downlink-codec", default="fp32",
                   help="cut-layer downlink codec (gradient broadcast/unicast)")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--optimizer", default="sgd")
    p.add_argument("--dataset", default="mnist")
    p.add_argument("--n-samples", type=int, default=2000)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--metrics-dir", default=None,
                   help="enable the obs recorder: JSONL events + manifest "
                        "into this directory (repro.obs; render with "
                        "python -m repro.obs.report DIR)")
    p.add_argument("--profile", type=int, default=None, metavar="N",
                   help="capture a jax.profiler trace of round/step N "
                        "(written under --metrics-dir, or ./profile)")
    p.add_argument("--quiet", action="store_true",
                   help="suppress the stderr progress log (events still "
                        "recorded when --metrics-dir is set)")
    args = p.parse_args(argv)
    enable_compile_cache()
    # recorder BEFORE any simulator/engine construction: instrumented
    # objects capture the active recorder when they are built
    rec = None
    if args.metrics_dir:
        rec = obs.Recorder(args.metrics_dir, quiet=args.quiet,
                           append=bool(args.resume), config=vars(args))
        obs.set_recorder(rec)
    obs.set_quiet(args.quiet)
    try:
        if args.arch.startswith("paper-cnn"):
            return train_cnn(args)
        return train_lm(args)
    finally:
        if rec is not None:
            rec.close()
            obs.set_recorder(None)
        obs.set_quiet(False)


if __name__ == "__main__":
    main()
