"""Datacenter-scale SFL train/serve steps for the LLM zoo.

Parameter layout under split training:

* ``params["client"]`` — embedding + layers[:cut], with a **leading client
  axis N** (each federated client owns its own copy; sharded over the
  ("pod","data") mesh axes so the copy lives where its data lives).
* ``params["server"]`` — layers[cut:] + final norm + head, shared (the
  τ=1 equivalent of the paper's per-client server replicas + eq. 7
  aggregation; see DESIGN.md §2).

Algorithms:

* ``sfl_ga`` — gradagg() at the boundary (one X(v)-byte all-reduce);
  client params get NO cross-client collective (the paper's saving).
* ``sfl``    — per-client cotangents; client params ρ-averaged every round
  (an extra φ(v)-byte all-reduce — the traffic SFL-GA removes).
* ``psl``    — per-client cotangents, no client averaging (personalized).

Scheme semantics and the cut-layer transport come from
``core.protocol.ProtocolEngine`` — the same engine the CNN simulator
runs — so ``TrainConfig(uplink_codec=..., downlink_codec=..., tau=...)``
gives every LLM workload the compressed boundary
(``make_gradagg_compressed``) and τ>1 local steps (one ``lax.scan`` over
the local-epoch axis). Defaults (fp32, τ=1) are bit-identical to the
pre-engine steps.

Batch layout: tokens/labels (N, B/N, S) — the leading axis is the client
axis, sharded over ("pod","data"). With τ>1 a local-epoch axis follows
the client axis: (N, τ, B/N, S). An optional ``batch["seed"]`` uint32
drives the codecs' stochastic rounding (see DESIGN.md §2.2).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig, TrainConfig
from repro.core.bank import broadcast_row0, gather_rows, scatter_rows
from repro.core.gradagg import uniform_rho
from repro.core.protocol import ProtocolEngine, aggregate_cohort
from repro.models import lm as lm_mod
from repro.models import transformer as tf
from repro.optim.optimizers import Optimizer, apply_updates

ALGOS = ("sfl_ga", "sfl", "psl")


def split_lm_params(params: Dict, n_clients: int) -> Dict:
    """Re-layout init_lm() output into {client: stacked, server: flat}.

    All clients start from the same w^c_0 (paper §II-B), so stacking is a
    broadcast of the shared init.
    """
    client = {"embed": params["embed"], "groups": params["client"]}
    client = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_clients,) + x.shape), client)
    server = {"groups": params["server"], "final_norm": params["final_norm"]}
    if "head" in params:
        server["head"] = params["head"]
    return {"client": client, "server": server}


def split_lm_lora_params(params: Dict, loras: Dict, n_clients: int) -> Dict:
    """PEFT layout (DESIGN.md §17): the FEDERATED unit is the adapter tree.

    ``client``/``server`` hold only trainable adapters (client stacked to
    (N,) like the full path — so the bank, cohort gather/scatter and the
    aggregation rules apply unchanged, just orders of magnitude smaller);
    the frozen ``init_lm`` tree rides under ``base``, logically replicated
    on both sides of every cut — it never crosses the wire."""
    client = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_clients,) + x.shape),
        {"groups": loras["client"]})
    return {"client": client, "server": {"groups": loras["server"]},
            "base": params}


def trainable_params(split: Dict) -> Dict:
    """The trainable partition of a split tree: everything except the
    frozen base. On a full-parameter tree this is the whole tree, so
    ``opt.init(trainable_params(p))`` is layout-agnostic — under PEFT the
    optimizer moments exist only for adapter leaves."""
    return {k: v for k, v in split.items() if k != "base"}


def _ungroup_layers(groups_params, groups, layer_axis: int) -> list:
    """Flatten scan-stacked group params into a per-layer list of trees.

    A group with repeat R and period p covers R·p layers in r-major order;
    ``layer_axis`` is 0 for server-side params and 1 for client-side ones
    (whose leaves carry a leading client axis N)."""
    layers = []
    for g, gp in zip(groups, groups_params):
        for r in range(g.repeat):
            for i in range(len(g.period)):
                layers.append(jax.tree.map(
                    lambda x: jax.lax.index_in_dim(x, r, layer_axis,
                                                   keepdims=False), gp[i]))
    return layers


def _regroup_layers(layers: list, groups, layer_axis: int) -> list:
    """Inverse of ``_ungroup_layers`` for a (possibly different) grouping."""
    out, k = [], 0
    for g in groups:
        p = len(g.period)
        out.append(tuple(
            jax.tree.map(lambda *xs: jnp.stack(xs, axis=layer_axis),
                         *[layers[k + r * p + i] for r in range(g.repeat)])
            for i in range(p)))
        k += g.repeat * p
    return out


def _move_split_layers(client_layers: list, server_layers: list,
                       old_v: int, new_v: int, n: int, w) -> tuple:
    """Shared cut-move core: per-layer trees cross the boundary, with
    server→client broadcast to N copies and client→server anchored-delta
    ρ-average (exact — bit-identical — whenever the copies agree, making
    v→v'→v round-trips lossless from equal copies)."""
    if new_v > old_v:  # server→client: broadcast shared layers to N clients
        moving = server_layers[:new_v - old_v]
        server_layers = server_layers[new_v - old_v:]
        client_layers += [jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), l)
            for l in moving]
    else:              # client→server: ρ-average the per-client copies
        moving = client_layers[new_v:]
        client_layers = client_layers[:new_v]

        def mean(p):
            # anchored-delta ρ-average: base + Σ ρ_i (p_i − base) is the
            # same weighted mean but EXACT (bit-identical) when the client
            # copies agree — which makes v→v'→v round-trips lossless from
            # equal copies, the property the migration tests pin.
            p32 = p.astype(jnp.float32)
            ww = w.reshape((n,) + (1,) * (p.ndim - 1)).astype(jnp.float32)
            return (p32[0] + jnp.sum(ww * (p32 - p32[0][None]), axis=0)) \
                .astype(p.dtype)

        server_layers = [jax.tree.map(mean, l) for l in moving] + server_layers
    return client_layers, server_layers


def resplit_lm_params(split: Dict, old_plan: lm_mod.ModelPlan,
                      new_plan: lm_mod.ModelPlan,
                      rho: Optional[jnp.ndarray] = None) -> Dict:
    """Migrate the split layout from ``old_plan.cut`` to ``new_plan.cut``.

    Layers moving server→client are broadcast to every client (each gets
    its own copy of the shared server layer); layers moving client→server
    collapse the N per-client copies into one shared layer by ρ-average —
    the eq.-7-style merge, exact (and v→v'→v lossless) whenever the client
    copies agree, which holds at init and for client-aggregating schemes.
    Works on any tree with the params structure, so optimizer moments
    migrate through the same function (see ``resplit_opt_state``).

    Under PEFT (``old_plan.peft`` set) the tree holds ADAPTERS — same
    machinery, orders-of-magnitude smaller payload — and the frozen base
    (when present under ``"base"``) is re-partitioned by pure relayout via
    :func:`resplit_base_params`: it is replicated on both sides of the
    cut, so no averaging, no broadcast-to-N, no wire cost.
    """
    old_v, new_v = old_plan.cut, new_plan.cut
    assert min(old_v, new_v) >= 1, "dynamic cut needs a client side (v >= 1)"
    if old_v == new_v:
        return split
    n = jax.tree.leaves(split["client"])[0].shape[0]
    w = uniform_rho(n) if rho is None else rho

    client_layers = _ungroup_layers(split["client"]["groups"],
                                    old_plan.client_groups, layer_axis=1)
    server_layers = _ungroup_layers(split["server"]["groups"],
                                    old_plan.server_groups, layer_axis=0)
    client_layers, server_layers = _move_split_layers(
        client_layers, server_layers, old_v, new_v, n, w)

    client = {"groups": _regroup_layers(client_layers,
                                        new_plan.client_groups, layer_axis=1)}
    if "embed" in split["client"]:  # full path; adapter trees have no embed
        client["embed"] = split["client"]["embed"]
    server = dict(split["server"],
                  groups=_regroup_layers(server_layers,
                                         new_plan.server_groups, layer_axis=0))
    out = {"client": client, "server": server}
    if "base" in split:
        out["base"] = resplit_base_params(split["base"], old_plan, new_plan)
    return out


def resplit_base_params(base: Dict, old_plan: lm_mod.ModelPlan,
                        new_plan: lm_mod.ModelPlan) -> Dict:
    """Re-partition a frozen ``init_lm``-shaped base across a cut change.

    Both sides hold the SAME shared weights (one copy each — the client
    stack is not per-client under PEFT), so a cut move is a relayout of
    the scan stacking: ungroup → slice at the new cut → regroup. Nothing
    is averaged and nothing crosses the wire — this is why PEFT migration
    prices only the adapter sliver."""
    if old_plan.cut == new_plan.cut:
        return base
    layers = (_ungroup_layers(base["client"], old_plan.client_groups, 0)
              + _ungroup_layers(base["server"], old_plan.server_groups, 0))
    v = new_plan.cut
    return dict(
        base,
        client=_regroup_layers(layers[:v], new_plan.client_groups, 0),
        server=_regroup_layers(layers[v:], new_plan.server_groups, 0))


def resplit_opt_state(opt_state: Dict, old_plan: lm_mod.ModelPlan,
                      new_plan: lm_mod.ModelPlan,
                      rho: Optional[jnp.ndarray] = None) -> Dict:
    """Migrate optimizer state across a cut change: params-shaped subtrees
    (adamw m/v, momentum mu) go through ``resplit_lm_params``; scalar
    fields (count) pass through untouched."""
    out = dict(opt_state)
    for k in ("m", "v", "mu"):
        if k in out:
            out[k] = resplit_lm_params(out[k], old_plan, new_plan, rho)
    return out


def gather_cohort(tree: Dict, idx) -> Dict:
    """Slice the client bank to the round's cohort rows (DESIGN.md §13).

    ``tree`` is any params-shaped {client, server} dict — the params
    themselves or one optimizer moment. The server side is shared (O(1)
    in N) and passes through; client leaves lose their (N,) bank axis
    for a (K,) cohort axis, ready for the jitted train step. Host span
    ``bank/gather``: one compiled program for the whole client tree
    (``core.bank.gather_rows``)."""
    with obs.get_recorder().span("bank/gather"):
        return dict(tree,
                    client=gather_rows(tree["client"], jnp.asarray(idx)))


def scatter_cohort(bank: Dict, cohort: Dict, idx,
                   broadcast_client: bool = False) -> Dict:
    """Fold a trained cohort back into the bank: the shared server side
    replaces wholesale; client rows scatter to their bank slots
    (duplicate indices — the ρ sampler's with-replacement draws —
    resolve arbitrarily, each being an independent local update of the
    same client). ``broadcast_client=True`` writes cohort row 0 to EVERY
    bank row — the client-aggregating schemes (sfl), whose train step
    already made all cohort rows the new global client model. Host span
    ``bank/scatter``: one compiled program for the whole client tree
    (``core.bank.scatter_rows`` or ``broadcast_row0``)."""
    with obs.get_recorder().span("bank/scatter"):
        if broadcast_client:
            client = broadcast_row0(bank["client"], cohort["client"])
        else:
            client = scatter_rows(bank["client"], cohort["client"],
                                  jnp.asarray(idx))
        return dict(bank, client=client, server=cohort["server"])


def gather_cohort_opt(opt_state: Dict, idx) -> Dict:
    """Cohort slice of the optimizer state: params-shaped moments (adamw
    m/v, momentum mu) gather like params; scalars (count) pass through."""
    with obs.get_recorder().span("bank/gather"):
        out = dict(opt_state)
        for k in ("m", "v", "mu"):
            if k in out:
                out[k] = gather_cohort(out[k], idx)
        return out


def scatter_cohort_opt(bank_opt: Dict, cohort_opt: Dict, idx) -> Dict:
    """Inverse of ``gather_cohort_opt``. Moments always scatter per-row
    (each client keeps its OWN moment history even under sfl's parameter
    aggregation); scalars (count) come from the cohort run."""
    with obs.get_recorder().span("bank/scatter"):
        out = dict(cohort_opt)
        for k in ("m", "v", "mu"):
            if k in out:
                out[k] = scatter_cohort(bank_opt[k], cohort_opt[k], idx)
        return out


def merge_lm_params(split: Dict, rho: Optional[jnp.ndarray] = None) -> Dict:
    """Global eval/serve model: ρ-weighted mean of client copies + server."""
    n = jax.tree.leaves(split["client"])[0].shape[0]
    w = (uniform_rho(n) if rho is None else rho)

    def mean(p):
        ww = w.reshape((n,) + (1,) * (p.ndim - 1)).astype(jnp.float32)
        return jnp.sum(p.astype(jnp.float32) * ww, axis=0).astype(p.dtype)

    client = jax.tree.map(mean, split["client"])
    out = {"embed": client["embed"], "client": client["groups"],
           "server": split["server"]["groups"],
           "final_norm": split["server"]["final_norm"]}
    if "head" in split["server"]:
        out["head"] = split["server"]["head"]
    return out


def merge_lm_lora_params(split: Dict,
                         rho: Optional[jnp.ndarray] = None) -> Dict:
    """PEFT analogue of :func:`merge_lm_params`: ρ-mean the per-client
    adapter rows, fold them into the frozen base (w' = w + s·AB), return
    a plain ``init_lm``-shaped tree every non-PEFT consumer understands."""
    n = jax.tree.leaves(split["client"])[0].shape[0]
    w = uniform_rho(n) if rho is None else rho

    def mean(p):
        ww = w.reshape((n,) + (1,) * (p.ndim - 1)).astype(jnp.float32)
        return jnp.sum(p.astype(jnp.float32) * ww, axis=0).astype(p.dtype)

    cad = jax.tree.map(mean, split["client"])
    return lm_mod.merge_lm_loras(
        split["base"], {"client": cad["groups"],
                        "server": split["server"]["groups"]})


def _client_forward_one(cparams, plan, tokens, inputs_embeds, impl, remat, dtype):
    full = {"embed": cparams["embed"], "client": cparams["groups"]}
    return lm_mod.client_forward(full, plan, tokens, inputs_embeds,
                                 impl=impl, remat=remat, dtype=dtype)


def _server_forward(sparams, plan, smashed, impl, remat):
    full = {"client": [], "server": sparams["groups"],
            "final_norm": sparams["final_norm"]}
    if "head" in sparams:
        full["head"] = sparams["head"]
    return lm_mod.server_forward(full, plan, smashed, impl=impl, remat=remat)


def _engine_for(tcfg: TrainConfig) -> ProtocolEngine:
    return ProtocolEngine(tcfg.algo, tcfg.uplink_codec, tcfg.downlink_codec,
                          base_seed=tcfg.seed,
                          adapter_sync=(tcfg.peft != "none"))


def make_loss_fn(plan: lm_mod.ModelPlan, tcfg: TrainConfig,
                 rho: jnp.ndarray,
                 engine: Optional[ProtocolEngine] = None) -> Callable:
    cfg = plan.cfg
    dtype = jnp.dtype(tcfg.compute_dtype)
    impl = "jnp"
    engine = _engine_for(tcfg) if engine is None else engine
    peft = plan.peft is not None

    def loss_fn(params, batch, seed=0, rho_w=None):
        # rho_w: cohort aggregation weights replacing the full-bank ρ
        # over the K gathered participants (None = full participation)
        r = rho if rho_w is None else rho_w
        tokens = batch["tokens"]  # (N, b, S) int32 — or embeds (N, b, S, d)
        labels = batch["labels"]  # (N, b, S)
        n = tokens.shape[0]
        if peft:
            # PEFT: per-client trees are adapter slivers; the frozen base
            # is shared (closed over → unbatched under vmap) and attached
            # structurally at trace time. Only params["client"]/["server"]
            # are differentiated — see _make_local_step.
            base = params["base"]

            def cfwd(ad, toks, embeds):
                full = {"embed": base["embed"],
                        "client": tf.attach_group_loras(base["client"],
                                                        ad["groups"])}
                return lm_mod.client_forward(full, plan, toks, embeds,
                                             impl=impl, remat=tcfg.remat,
                                             dtype=dtype)

            sgroups = tf.attach_group_loras(base["server"],
                                            params["server"]["groups"])
            sparams = {"groups": sgroups, "final_norm": base["final_norm"]}
            if "head" in base:
                sparams["head"] = base["head"]
        else:
            def cfwd(cp, toks, embeds):
                return _client_forward_one(cp, plan, toks, embeds, impl,
                                           tcfg.remat, dtype)

            sparams = params["server"]
        if jnp.issubdtype(tokens.dtype, jnp.floating):
            # stubbed-modality inputs: precomputed embeds
            smashed, aux_c = jax.vmap(
                lambda cp, e: cfwd(cp, None, e)
            )(params["client"], tokens.astype(dtype))
        else:
            smashed, aux_c = jax.vmap(
                lambda cp, t: cfwd(cp, t, None)
            )(params["client"], tokens)
        # the scheme's cut-layer transport: lossy uplink forward; eq.-5
        # aggregate-broadcast (sfl_ga) or per-client unicast backward
        smashed = engine.boundary(smashed, r, seed)
        nb, b, S, d = smashed.shape
        logits, aux_s = _server_forward(sparams, plan,
                                        smashed.reshape(nb * b, S, d),
                                        impl, tcfg.remat)
        ce = lm_mod.cross_entropy(logits, labels.reshape(nb * b, S))
        loss = ce + 0.01 * (jnp.sum(aux_c) + aux_s)
        return loss, {"ce": ce}

    return loss_fn


def _make_local_step(loss_fn: Callable, opt: Optimizer,
                     peft: bool) -> Callable:
    """One optimizer step. Full path: differentiate the whole split tree
    (byte-identical to the pre-PEFT step). PEFT path: the frozen base is
    held out as a non-differentiated argument, so grads — and the
    optimizer state threaded through — exist only for adapter leaves,
    and the step returns only the trainable ``{client, server}`` tree.
    The caller keeps the base: a jitted step that returned it unchanged
    would copy every base leaf into a fresh output buffer each call."""
    if not peft:
        def local_step(params, opt_state, batch, seed, w):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, seed, w)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return params, opt_state, dict(metrics, loss=loss)

        return local_step

    def tr_loss(tr, base, batch, seed, w):
        return loss_fn(dict(tr, base=base), batch, seed, w)

    def local_step(params, opt_state, batch, seed, w):
        tr = trainable_params(params)
        (loss, metrics), grads = jax.value_and_grad(
            tr_loss, has_aux=True)(tr, params["base"], batch, seed, w)
        updates, opt_state = opt.update(grads, opt_state, tr)
        tr = apply_updates(tr, updates)
        return tr, opt_state, dict(metrics, loss=loss)

    return local_step


def _local_steps(local_step: Callable, engine: ProtocolEngine, tau: int,
                 params, opt_state, batch, seed, w):
    """τ local steps from ``params``; returns ``(trained, opt_state,
    metrics)``. A frozen PEFT base is read once and closed over: it is
    never in the scan's carry, nor in what comes back."""
    if tau == 1:
        return local_step(params, opt_state, batch, seed, w)
    # τ local steps: tokens/labels carry a local-epoch axis
    # (N, τ, b, S[, d]); scan over it with per-epoch codec seeds.
    want = 5 if jnp.issubdtype(batch["tokens"].dtype, jnp.floating) else 4
    assert batch["tokens"].ndim == want, (
        f"tau={tau} needs a local-epoch axis: tokens (N, tau, b, S"
        f"{', d' if want == 5 else ''}), got {batch['tokens'].shape}")
    xs = jnp.moveaxis(batch["tokens"], 1, 0)
    ys = jnp.moveaxis(batch["labels"], 1, 0)
    seeds = engine.epoch_seeds(seed, xs.shape[0])
    frozen = {k: v for k, v in params.items() if k == "base"}

    def body(carry, sl):
        p, s = carry
        t, l, sd = sl
        p, s, m = local_step(dict(p, **frozen), s,
                             {"tokens": t, "labels": l}, sd, w)
        return (p, s), m

    (params, opt_state), ms = jax.lax.scan(
        body, (trainable_params(params), opt_state), (xs, ys, seeds))
    return params, opt_state, jax.tree.map(jnp.mean, ms)


def make_train_step(plan: lm_mod.ModelPlan, tcfg: TrainConfig, opt: Optimizer,
                    n_clients: int, rho: Optional[jnp.ndarray] = None,
                    engine: Optional[ProtocolEngine] = None) -> Callable:
    """``train_step(params, opt_state, batch) -> (trained, opt_state,
    metrics)``: τ local steps, then the scheme's round-end aggregation.
    On a full-parameter tree ``trained`` is the whole new tree; with a
    PEFT plan it is only the trainable ``{client, server}`` adapter tree,
    and the caller keeps the frozen ``base`` it passed in (DESIGN.md
    §17)."""
    assert tcfg.algo in ALGOS, tcfg.algo
    rho = uniform_rho(n_clients) if rho is None else rho
    # launchers pass their own engine when they attach an obs traffic
    # ledger (the taps must live in the SAME engine the step traces)
    engine = _engine_for(tcfg) if engine is None else engine
    loss_fn = make_loss_fn(plan, tcfg, rho, engine=engine)
    tau = tcfg.resolved_tau
    local_step = _make_local_step(loss_fn, opt, plan.peft is not None)

    def train_step(params, opt_state, batch):
        seed = batch.get("seed", 0)
        # cohort weights over the gathered participants (DESIGN.md §13);
        # absent = full participation, bit-identical to the pre-cohort step
        w = batch.get("rho")
        # anchor for the partial-cohort aggregate: the model every
        # participant STARTED from (rows are identical — the previous
        # round broadcast the aggregate into the bank), so row 0
        client0 = jax.tree.map(lambda x: x[0], params["client"]) \
            if (w is not None and engine.spec.client_aggregate) else None
        params, opt_state, metrics = _local_steps(
            local_step, engine, tau, params, opt_state, batch, seed, w)
        if engine.spec.client_aggregate:
            # traditional SFL: aggregate client-side models every round —
            # the φ(v)-byte collective SFL-GA eliminates.
            engine.tap_model_sync(params["client"])
            if w is None:
                client = engine.aggregate(params["client"], rho)
            else:
                # partial cohort: unbiased anchored-delta aggregate
                # (weights need not sum to 1), broadcast back over the
                # cohort axis for the launcher's bank scatter
                agg = aggregate_cohort(params["client"], w, anchor=client0)
                client = jax.tree.map(
                    lambda a, like: jnp.broadcast_to(
                        a[None], like.shape).astype(like.dtype),
                    agg, params["client"])
            params = dict(params, client=client)
        return params, opt_state, metrics

    return train_step


def make_gen_step(plan: lm_mod.ModelPlan, tcfg: TrainConfig, opt: Optimizer,
                  k: int, rho: Optional[jnp.ndarray] = None,
                  engine: Optional[ProtocolEngine] = None) -> Callable:
    """Dispatch-time compute for the buffered-async LM path (DESIGN.md
    §16): the exact τ-step local training of ``make_train_step``, minus
    the round-end aggregation — the engine staleness-weights the merges
    instead. Returns ``(loss, server_delta, client)``: the server-side
    DELTA against the dispatch-time model (``protocol.merge_async``
    folds it into the live server at merge time) and the absolute
    client rows (sfl_ga / psl personalize client sides; they scatter
    back into the bank as-is).

    Scope: schemes WITHOUT client aggregation (sfl_ga / psl) and
    stateless-per-client optimizers (sgd) — staleness-discounting
    per-client optimizer moments is not defined here."""
    assert tcfg.algo in ALGOS, tcfg.algo
    engine = _engine_for(tcfg) if engine is None else engine
    if engine.spec.client_aggregate:
        raise ValueError(
            f"async LM path covers sfl_ga/psl (personalized client "
            f"sides); {tcfg.algo!r} aggregates client models every round")
    rho = uniform_rho(k) if rho is None else rho
    loss_fn = make_loss_fn(plan, tcfg, rho, engine=engine)
    tau = tcfg.resolved_tau
    local_step = _make_local_step(loss_fn, opt, plan.peft is not None)

    def gen_step(params, opt_state, batch):
        seed = batch.get("seed", 0)
        w = batch.get("rho")
        server0 = params["server"]
        params, opt_state, metrics = _local_steps(
            local_step, engine, tau, params, opt_state, batch, seed, w)
        delta = jax.tree.map(
            lambda p, a: p.astype(jnp.float32) - a.astype(jnp.float32),
            params["server"], server0)
        return {"loss": metrics["loss"], "server_delta": delta,
                "client": params["client"]}, opt_state

    return gen_step


# ---------------------------------------------------------------------------
# Serving steps (used by the decode/prefill dry-run shapes)
# ---------------------------------------------------------------------------

def make_prefill_step(plan: lm_mod.ModelPlan, dtype=jnp.bfloat16) -> Callable:
    def prefill_step(params, batch):
        logits, caches = lm_mod.prefill(
            params, plan, tokens=batch.get("tokens"),
            inputs_embeds=batch.get("inputs_embeds"),
            max_len=batch["tokens"].shape[1] if "tokens" in batch
            else batch["inputs_embeds"].shape[1],
            dtype=dtype)
        return logits, caches

    return prefill_step


def make_decode_step(plan: lm_mod.ModelPlan, dtype=jnp.bfloat16) -> Callable:
    def decode_step(params, token, caches):
        return lm_mod.decode_step(params, plan, token, caches, dtype=dtype)

    return decode_step


# ---------------------------------------------------------------------------
# Communication accounting (bytes per round) — paper Fig. 4 at LLM scale
# ---------------------------------------------------------------------------

def comm_bytes_per_round(cfg: ModelConfig, plan: lm_mod.ModelPlan, algo: str,
                         n_clients: int, per_client_batch: int, seq: int,
                         tau: int = 1, bytes_per_elem: int = 2,
                         uplink_codec: str = "fp32",
                         downlink_codec: str = "fp32") -> Dict[str, int]:
    """Edge-protocol traffic accounting (who sends what over the WAN).

    ``n_clients`` is the round's PARTICIPANT count — under partial
    participation pass the cohort size K (idle bank entries send
    nothing). Thin adapter over the unified ``sysmodel.traffic``
    accounting: this
    function only supplies the LLM's element counts — X(v) smashed-data
    elements per client per epoch, φ(v) client-model bytes. Codecs price
    the cut-layer payloads; labels and model sync stay at the raw
    ``bytes_per_elem`` wire precision.
    """
    from repro.core.split import (client_adapter_numel, client_param_numel,
                                  total_param_numel)
    from repro.sysmodel.traffic import round_traffic_bytes

    be8 = bytes_per_elem * 8
    peft = plan.peft is not None
    return round_traffic_bytes(
        algo, n_clients=n_clients, tau=tau,
        smashed_elems=per_client_batch * seq * cfg.d_model,
        label_bits=per_client_batch * seq * 32,
        client_model_bits=0 if peft else client_param_numel(plan) * be8,
        adapter_model_bits=client_adapter_numel(plan) * be8 if peft else 0,
        full_model_bits=total_param_numel(plan) * be8
        if (algo == "fl" and not peft) else 0,
        uplink_codec=uplink_codec, downlink_codec=downlink_codec,
        raw_bits_per_elem=be8)


def comm_breakdown_per_round(cfg: ModelConfig, plan: lm_mod.ModelPlan,
                             algo: str, n_clients: int,
                             per_client_batch: int, seq: int, tau: int = 1,
                             bytes_per_elem: int = 2,
                             uplink_codec: str = "fp32",
                             downlink_codec: str = "fp32") -> Dict[str, int]:
    """Per-category (obs-ledger) view of ``comm_bytes_per_round`` — in
    BITS, the reconciliation target for the LLM path's traffic ledger.
    Model-sync payloads price the CLIENT-side parameters at the raw wire
    precision, matching ``ProtocolEngine.tap_model_sync``."""
    from repro.core.split import (client_adapter_numel, client_param_numel,
                                  total_param_numel)
    from repro.sysmodel.traffic import round_traffic_breakdown

    be8 = bytes_per_elem * 8
    peft = plan.peft is not None
    return round_traffic_breakdown(
        algo, n_clients=n_clients, tau=tau,
        smashed_elems=per_client_batch * seq * cfg.d_model,
        label_bits=per_client_batch * seq * 32,
        client_model_bits=0 if peft else client_param_numel(plan) * be8,
        adapter_model_bits=client_adapter_numel(plan) * be8 if peft else 0,
        full_model_bits=total_param_numel(plan) * be8
        if (algo == "fl" and not peft) else 0,
        uplink_codec=uplink_codec, downlink_codec=downlink_codec,
        raw_bits_per_elem=be8)


# ---------------------------------------------------------------------------
# Whisper (enc-dec) split training — smashed data = (residual, enc states)
# ---------------------------------------------------------------------------

def make_whisper_train_step(cfg: ModelConfig, tcfg: TrainConfig, opt: Optimizer,
                            n_clients: int, rho: Optional[jnp.ndarray] = None):
    from repro.models import encdec

    assert tcfg.algo in ALGOS
    assert tcfg.resolved_tau == 1, "tau>1 not wired for enc-dec training"
    rho = uniform_rho(n_clients) if rho is None else rho
    dtype = jnp.dtype(tcfg.compute_dtype)
    engine = _engine_for(tcfg)

    def loss_fn(params, batch, seed=0):
        fe = batch["frame_embeds"].astype(dtype)  # (N, b, F, d)
        toks, labels = batch["tokens"], batch["labels"]  # (N, b, S)
        x, enc = jax.vmap(
            lambda cp, f, t: encdec.whisper_client_forward(cp, cfg, f, t, dtype)
        )(params["client"], fe, toks)
        # both boundary tensors cross the scheme's transport (eq. 5 for
        # sfl_ga: aggregated + broadcast; unicast for sfl/psl)
        x = engine.boundary(x, rho, seed)
        enc = engine.boundary(enc, rho, seed, tap_labels=False)
        n, b = x.shape[:2]
        logits = encdec.whisper_server_forward(
            params["server"], cfg, x.reshape((n * b,) + x.shape[2:]),
            enc.reshape((n * b,) + enc.shape[2:]))
        ce = lm_mod.cross_entropy(logits, labels.reshape(n * b, -1))
        return ce, {"ce": ce}

    def train_step(params, opt_state, batch):
        seed = batch.get("seed", 0)
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, seed)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        if engine.spec.client_aggregate:
            engine.tap_model_sync(params["client"])
            params = dict(params,
                          client=engine.aggregate(params["client"], rho))
        return params, opt_state, dict(metrics, loss=loss)

    return train_step
