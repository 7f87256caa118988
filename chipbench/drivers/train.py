"""Driver of split LoRA training: the launcher's LM loop, from library calls.

Per step, as ``repro.launch.train`` runs it: the harness's batch →
``sampler.cohort`` → ``gather_cohort``/``gather_cohort_opt`` → the jitted
``make_train_step`` → ``scatter_cohort``/``scatter_cohort_opt``; the step's
loss is read back to the host.

Set-up builds the step and its state once and drives its first
``check_steps`` steps through that same loop (they compile it), reading
each loss as its step ends; the window goes on from there with the same
objects. In the window the host dispatches up to ``AHEAD_S`` seconds of
steps ahead of the loss it reads, so that a stall of the host does not
idle the device; when the time is up it dispatches nothing more, reads
every loss still due, and only then reads the clock: every dispatched step
counts, over all of that time. After the window the program's
state is dropped and the plain reference replays those first steps from the
same weights and batches: each step's loss, the first gradient as SGD got
it (from the parameters after step 1) and the change after the last are
compared leaf by leaf.
"""
from __future__ import annotations

import collections
import dataclasses
import sys
import time

import numpy as np

from chipbench import flops, generate, weights

# seconds of steps in flight ahead of the loss the window's loop reads
AHEAD_S = 6.0


def _tuple(v):
    return tuple(v) if isinstance(v, list) else v


def model_config(config: dict):
    """The program's ``ModelConfig`` from a configuration file: the keys it
    has a field for (the harness's own, such as ``cut``, stay out), nested
    ``moe``/``ssm`` objects as their dataclasses and lists as tuples, so
    that the result is hashable."""
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

    nested = {"moe": MoEConfig, "ssm": SSMConfig}
    keys = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: _tuple(v) for k, v in config.items() if k in keys}
    for k, cls in nested.items():
        if kw.get(k) is not None:
            kw[k] = cls(**{f: _tuple(v) for f, v in kw[k].items()})
    return ModelConfig(**kw)


# what a configuration file says about itself, not about the model
DESCRIPTIVE = ("name", "source", "published", "reduced", "assumed",
               "deployment", "reference")


def reference_config(config: dict, traffic: dict) -> dict:
    """Every architecture key of the configuration, nested objects
    included, and the traffic's LoRA and page keys."""
    out = {k: v for k, v in config.items() if k not in DESCRIPTIVE}
    out.update({k: traffic[k] for k in ("page_size", "lora_rank",
                                        "lora_alpha") if k in traffic})
    return out


def _host(tree):
    import jax

    return jax.tree.map(lambda a: np.array(a), tree)


def _plain_trainable(tr, cut):
    return {"client": weights.plain_adapters(tr["client"]["groups"], 0, True),
            "server": weights.plain_adapters(tr["server"]["groups"], cut,
                                             False)}


def _leaves(tree, prefix=""):
    """{dotted path: array} of a nested dict."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_leaves(v, f"{prefix}{k}."))
        else:
            out[prefix + k] = np.asarray(v, np.float64)
    return out


def norm_gap(prog: dict, ref: dict):
    """Worst leaf of |‖prog‖ − ‖ref‖| / max(‖ref‖, median leaf's ‖ref‖),
    over the leaves whose reference norm is at least a thousandth of the
    median leaf's (leaves the reference does not move are left out)."""
    rn = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    med = float(np.median(list(rn.values())))
    kept = [k for k in rn if rn[k] >= 1e-3 * med]
    worst, leaf = 0.0, None
    for k in kept:
        g = abs(float(np.linalg.norm(prog[k])) - rn[k]) / max(rn[k], med)
        if g >= worst:
            worst, leaf = g, k
    return worst, leaf, len(kept), len(rn)


class Setup:
    """The program's step with its state, driven through its first steps."""

    def __init__(self, cell):
        import jax
        import jax.numpy as jnp

        from repro.configs.base import PeftSpec, TrainConfig
        from repro.core import algorithms as alg
        from repro.core.cohort import make_sampler
        from repro.models import lm
        from repro.models.transformer import layer_specs
        from repro.optim import make_optimizer

        cfgj, tj, seed = cell.config, cell.traffic, cell.seed
        self.cell, self.alg = cell, alg
        cfg = model_config(cfgj)
        cut = cfgj["cut"]
        codec = cfgj["boundary_codec"]
        N, K, b, S = tj["clients"], tj["cohort"], tj["batch"], tj["seq"]
        rank, alpha = tj["lora_rank"], tj["lora_alpha"]
        self.lr = lr = tj["lr"]
        peft = PeftSpec(kind="lora", rank=rank, alpha=alpha)
        plan = lm.build_plan(cfg, cut, peft=peft)
        dtype = cfgj["dtype"]
        tcfg = TrainConfig(model=cfg, algo=tj["scheme"], cut_layer=cut,
                           compute_dtype=dtype, param_dtype=dtype, lr=lr,
                           remat=False, tau=1, uplink_codec=codec,
                           downlink_codec=codec, peft="lora",
                           lora_rank=rank, lora_alpha=alpha, seed=0)

        def init(key):
            base = lm.init_lm(key, plan, jnp.dtype(dtype))
            loras = lm.init_lm_loras(key, plan, jnp.dtype(dtype))
            return alg.split_lm_lora_params(base, loras, N)

        shapes = jax.eval_shape(init, jax.random.key(0))
        self.params = weights.make(shapes, seed, alpha, rank, adapters=True,
                                   ssm=cfg.ssm)
        self.base = self.params["base"]
        opt = make_optimizer(tj["optimizer"], lr)
        self.opt_state = opt.init(alg.trainable_params(self.params))
        self.sampler = make_sampler(tj["sampler"], N, K,
                                    seed=seed & 0x7FFFFFFF)
        self.step_fn = jax.jit(alg.make_train_step(plan, tcfg, opt, K))
        self.codec_seed0 = int(generate.rng(seed, "order").integers(0, 2**32))
        self.shape, self.vocab = (K, b, S), cfg.vocab_size
        self.flops = flops.train_step_flops(
            cfgj, jax.eval_shape(weights.plain, shapes["base"])["layers"],
            kinds=layer_specs(cfg), cohort=K, batch=b, seq=S, rank=rank)
        # the first steps, through the window's own loop
        self.snaps = [self.trainable()]
        self.losses, self.fed, took = [], [], []
        n_check = tj["check_steps"]
        for i in range(n_check):
            t = time.perf_counter()
            loss, bt = self.one(i)
            self.losses.append(float(loss))
            took.append(time.perf_counter() - t)
            self.fed.append(bt)
            if i == 0 or i == n_check - 1:
                self.snaps.append(self.trainable())
        # steps in flight in the window: AHEAD_S over the fastest warm step
        self.ahead = max(1, round(AHEAD_S / min(took[1:] or took)))

    def trainable(self):
        return _host(self.alg.trainable_params(self.params))

    def batch(self, i):
        toks, labels = generate.token_batch(self.cell.seed, i, self.shape,
                                            self.vocab)
        return {"tokens": toks, "labels": labels,
                "seed": np.uint32((self.codec_seed0 + i * 1000003)
                                  & 0xFFFFFFFF)}

    def one(self, i):
        """One step of the launcher's loop, dispatched; returns (the loss on
        the device, what it fed)."""
        import jax.numpy as jnp

        alg, spans = self.alg, self.cell.spans
        with spans("batch prep"):
            bt = self.batch(i)
            dev = {"tokens": jnp.asarray(bt["tokens"]),
                   "labels": jnp.asarray(bt["labels"]), "seed": bt["seed"]}
            idx, w = self.sampler.cohort(i)
        with spans("cohort gather"):
            cp = alg.gather_cohort(self.params, idx)
            cop = alg.gather_cohort_opt(self.opt_state, idx)
        with spans("step"):
            cp, cop, m = self.step_fn(cp, cop, dict(dev, rho=jnp.asarray(w)))
        with spans("scatter"):
            self.params = alg.scatter_cohort(self.params, cp, idx,
                                             broadcast_client=False)
            self.opt_state = alg.scatter_cohort_opt(self.opt_state, cop, idx)
        return m["loss"], dict(bt, idx=np.asarray(idx), w=np.asarray(w))

    def drop(self):
        """Free the program's state; the weights the benchmark made stay."""
        import gc

        del self.params, self.opt_state, self.step_fn
        gc.collect()


def run(cell) -> dict:
    import jax

    st = Setup(cell)
    cell.compiles.active = True
    if cell.trace_dir:
        cell.start_trace(cell.trace_dir)
    t0 = time.perf_counter()
    setup_s = t0 - cell.t_process
    n, failed, first = 0, 0, len(st.fed)
    due = collections.deque()

    def read(loss):
        with cell.spans("loss read"):
            return int(not np.isfinite(float(loss)))

    with cell.spans("window"):
        while time.perf_counter() - t0 < cell.seconds:
            due.append(st.one(first + n)[0])
            n += 1
            if len(due) > st.ahead:
                failed += read(due.popleft())
        while due:
            failed += read(due.popleft())
        t1 = time.perf_counter()
    if cell.trace_dir:
        jax.profiler.stop_trace()
    cell.compiles.active = False
    window = t1 - t0
    print(f"window: {n} steps in {window!r} s, losses read up to "
          f"{st.ahead} steps late; compiles in window: "
          f"{cell.compiles.count}", file=sys.stderr)
    mem = cell.memory_peak()
    st.drop()
    ref = reference_run(cell, st.base, st.snaps[0], st.fed, st.lr)
    checks = compare(program_readings(st), ref)
    counts = {"steps": n, "window_s": window, "step_s": window / n,
              "step_flops": st.flops}
    return {"e2e": {"train_step_ms": 1e3 * window / n, "setup_s": setup_s},
            "counts": counts, "attempted": n, "failed": int(failed),
            "checks": checks, "memory_peak": mem}


def program_readings(st):
    """(losses, first gradient, change after the checked steps), leafwise
    in the reference's layout, from the program's snapshots."""
    cut = st.cell.config["cut"]
    l0, l1, l3 = (_leaves(_plain_trainable(s, cut)) for s in st.snaps)
    return (st.losses, {k: (l0[k] - l1[k]) / st.lr for k in l0},
            {k: l3[k] - l0[k] for k in l0})


def reference_run(cell, base, snap0, fed, lr, dtype=None, half=False):
    """The same readings from the plain reference (``dtype`` bfloat16 for
    the control; ``half``: a planted fault, the loss's mean over the first
    half of the cohort's rows only)."""
    import jax
    import jax.numpy as jnp

    ref = cell.reference
    rc = reference_config(cell.config, cell.traffic)
    dtype = dtype or jnp.float32

    @jax.jit
    def ref_step(base, tr, bt):
        return ref.train_step(rc, weights.plain(base), tr, bt, lr, dtype)

    p0 = _plain_trainable(snap0, rc["cut"])
    tr = jax.tree.map(jnp.asarray, p0)
    losses, g1 = [], None
    for bt in fed:
        feed = {k: jnp.asarray(bt[k]) for k in
                ("tokens", "labels", "idx", "w", "seed")}
        if half:
            k = feed["labels"].shape[0] // 2
            feed = dict(feed, tokens=feed["tokens"][:k],
                        labels=feed["labels"][:k], idx=feed["idx"][:k],
                        w=feed["w"][:k])
        loss, g, tr = ref_step(base, tr, feed)
        losses.append(float(loss))
        g1 = g if g1 is None else g1
    l0 = _leaves(p0)
    l3 = _leaves(jax.tree.map(np.asarray, tr))
    return (losses, _leaves(jax.tree.map(np.asarray, g1)),
            {k: l3[k] - l0[k] for k in l0})


def compare(prog, ref):
    """[(name, value)]: the worst step's loss gap, and the first-gradient
    and change gaps of the worst leaf."""
    (pl, pg, pc), (rl, rg, rcg) = prog, ref
    loss_gap = max(abs(a - r) / abs(r) for a, r in zip(pl, rl))
    grad_gap, gleaf, gk, gn = norm_gap(pg, rg)
    change_gap, cleaf, ck, cn = norm_gap(pc, rcg)
    print(f"reference losses {rl!r}; compared {pl!r}", file=sys.stderr)
    print(f"first gradient: worst leaf {gleaf} ({gk} of {gn} leaves moved); "
          f"change: worst leaf {cleaf} ({ck} of {cn})", file=sys.stderr)
    return [("loss_gap", loss_gap), ("grad_norm_gap", grad_gap),
            ("change_norm_gap", change_gap)]
