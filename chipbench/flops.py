"""Operations and bytes that a step needs, from the configuration's sizes
and, for an expert layer, from the weights the program holds.

An expert layer's widths are read from the layer's weight shapes in the
plain per-layer layout of ``chipbench/weights.py`` (``moe_router``,
``moe_w_gate``/``_up``/``_down``, ``moe_shared_gate``/``_up``/``_down``):
the experts held, their width, the router's width and the shared
expert's, so a layer that holds a share of its experts is counted by
that share. Only ``top_k``, a property of the router, comes from the
configuration.

"Needs" is the algorithm's count, not what the program happens to compute:
causal attention counts its lower triangle, a frozen weight's gradient is
not needed, and a decode step reads each live slot's context once. So a
share of a peak built on these counts cannot pass 100 % unless the time
leaves out part of the work. ``tests/test_flops.py`` and
``tests/test_families.py`` hold the counts against XLA's own
``cost_analysis`` of the program's steps, and the adapters against the
program's LoRA trees.
"""
from __future__ import annotations


DENSE = ("attn", "dense")
# the projections that the harness's LoRA adapts: the program's default
# ``PeftSpec.targets``; ``router`` is opt-in there
LORA_TARGETS = ("attn", "mlp", "ssm")


def _hd(cfg) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def _ssm(cfg):
    """(d_inner, heads, head_dim, groups, state) of the Mamba-2 mixer."""
    s = cfg["ssm"]
    d_inner = s["expand"] * cfg["d_model"]
    return (d_inner, d_inner // s["head_dim"], s["head_dim"], s["n_groups"],
            s["state_dim"])


def layer_modules(cfg, kind=DENSE) -> dict:
    """{module: {weight: (d_in, d_out)}} of the matmul weights that every
    token of one layer of ``kind`` runs through, keyed by the LoRA target
    that covers them (``attn``, ``ssm``, ``mlp``), from the
    configuration's sizes. An expert layer's router, shared expert and
    routed experts are none of them: ``expert_flops`` counts them from
    the layer's weights."""
    mixer, ffn = kind
    d = cfg["d_model"]
    out = {}
    if mixer == "attn":
        hd = _hd(cfg)
        out["attn"] = {"wq": (d, cfg["num_heads"] * hd),
                       "wk": (d, cfg["num_kv_heads"] * hd),
                       "wv": (d, cfg["num_kv_heads"] * hd),
                       "wo": (cfg["num_heads"] * hd, d)}
    else:
        d_inner, heads, _, groups, state = _ssm(cfg)
        out["ssm"] = {"in_proj": (d, 2 * d_inner + 2 * groups * state + heads),
                      "out_proj": (d_inner, d)}
    if ffn == "dense":
        f = cfg["d_ff"]
        out["mlp"] = {"up": (d, f), "down": (f, d)}
        if cfg["mlp_act"] == "swiglu":
            out["mlp"]["gate"] = (d, f)
    return out


def expert_flops(layer, top_k: int, tokens: int) -> int:
    """Forward and input gradient (4 per weight per token) of one expert
    layer's frozen matmuls over ``tokens``, read from the layer's weights
    (shapes suffice) in the plain layout of ``weights.plain``:

    * the router, ``(d, E_total)``, ``E_total`` the output axis of
      ``moe_router``: every token;
    * the shared expert, three matmuls of ``(d, F_sh)``, ``F_sh`` the
      output axis of ``moe_shared_gate``: every token; none where the
      layer has no ``moe_shared_*``;
    * the routed experts, three SwiGLU matmuls of ``(d, f)`` for each of
      the ``top_k`` assignments of a token that lands on an expert held
      here: ``E_held``, the leading axis of ``moe_w_gate`` (and of
      ``moe_w_up`` and ``moe_w_down``), of the router's ``E_total``.

    The routed term is an expectation over routing: a token's
    assignments land on the held experts in the share ``E_held /
    E_total``, in integer arithmetic (floored). With the seeded random
    router the share that a step realises varies from step to step, and
    tokens that a full expert drops are counted as served."""
    gate, up, down = (layer[k].shape
                      for k in ("moe_w_gate", "moe_w_up", "moe_w_down"))
    held, d, f = gate
    if up != gate or down != (held, f, d):
        raise ValueError(f"expert stacks disagree: {gate}, {up}, {down}")
    routed = layer["moe_router"].shape[1]
    shared = layer["moe_shared_gate"].shape[1] \
        if "moe_shared_gate" in layer else 0
    every = d * routed + 3 * d * shared
    return 4 * tokens * every + 4 * tokens * top_k * 3 * d * f * held // routed


def layer_mats(cfg, kind=DENSE) -> dict:
    """(d_in, d_out) of each of those weights of one layer."""
    return {k: v for mats in layer_modules(cfg, kind).values()
            for k, v in mats.items()}


def layer_params(cfg) -> int:
    """Matmul weights of one dense layer."""
    return sum(a * b for a, b in layer_mats(cfg).values())


def layer_lora(cfg, kind, rank: int) -> int:
    """Adapter weights (``A`` and ``B``) of one layer of ``kind``."""
    return sum(rank * (a + b) for t, m in layer_modules(cfg, kind).items()
               if t in LORA_TARGETS for a, b in m.values())


def head_params(cfg) -> int:
    return cfg["d_model"] * cfg["vocab_size"]


def weight_bytes(cfg, itemsize: int = 4) -> int:
    """Every weight a full forward of a dense model reads, embedding rows
    aside."""
    d, L = cfg["d_model"], cfg["num_layers"]
    bias = 0
    if cfg.get("attn_bias"):
        bias += (cfg["num_heads"] + 2 * cfg["num_kv_heads"]) * _hd(cfg)
    if cfg.get("mlp_bias"):
        bias += cfg["d_ff"] * (2 if cfg["mlp_act"] == "swiglu" else 1) + d
    per_layer = layer_params(cfg) + bias + 2 * d  # + two norm scales
    return itemsize * (L * per_layer + head_params(cfg) + d)


def causal_attn_flops(cfg, seq: int) -> int:
    """Scores and values of one causal sequence, one layer, forward."""
    return 2 * seq * seq * cfg["num_heads"] * _hd(cfg)


def ssd_flops(cfg, seq: int) -> int:
    """The chunked SSD of one causal sequence, one Mamba-2 layer, forward
    (arXiv:2405.21060 §6): within each chunk of ``Q`` positions the lower
    triangle of ``C B^T`` (per group) and its product with the values (per
    head), each chunk's final state ``B^T X`` and the states' outputs
    ``C h`` (per head), the recurrence over chunk states; a tail shorter
    than a chunk runs the recurrence a position at a time."""
    _, heads, p, groups, n = _ssm(cfg)
    q = cfg["ssm"]["chunk_size"]
    c, tail = divmod(seq, q)
    return (c * q * q * (groups * n + heads * p)
            + 4 * c * q * heads * n * p + 2 * c * heads * p * n
            + 4 * tail * heads * n * p)


def train_step_flops(cfg, layers, *, kinds, cohort: int, batch: int,
                     seq: int, rank: int) -> int:
    """A split LoRA step over ``cohort * batch`` sequences of ``seq``,
    counted layer by layer by its kind: ``kinds`` holds the (mixer, ffn)
    of each layer as the program's ``ModelConfig`` decides it
    (``transformer.layer_specs``), ``layers`` the weights of each layer in
    the plain layout, ``jax.eval_shape(weights.plain, base)["layers"]`` of
    the program's base tree (shapes suffice).

    Forward through every matmul and the head (2 per weight per token);
    backward needs activation gradients through every layer above the
    embedding (2 per weight per token), except the input gradients of the
    first layer's projections that read its input (q/k/v, or the Mamba-2
    ``in_proj``, and a parallel block's MLP): nothing below them trains.
    The adapters need their forward, input and weight
    gradients (6 per adapter weight per token). Causal attention and the
    SSD count forward once and backward twice. An expert layer counts as
    ``expert_flops`` reads it from ``layers``: its router and shared
    expert as matmuls, its routed experts as the expected share of the
    ``top_k`` SwiGLU experts a token (``cfg["moe"]["top_k"]``) that lands
    on the experts the layer holds; tokens a full expert drops are counted
    as served."""
    T, rows = cohort * batch * seq, cohort * batch
    total = 4 * head_params(cfg) * T
    for i, (kind, layer) in enumerate(zip(kinds, layers, strict=True)):
        mats = layer_mats(cfg, kind)
        total += 4 * T * sum(a * b for a, b in mats.values())
        total += 6 * T * layer_lora(cfg, kind, rank)
        if i == 0:
            first = {"wq", "wk", "wv", "in_proj"}
            if cfg.get("parallel_block") and kind == DENSE:
                first |= {"gate", "up"}
            total -= 2 * T * sum(a * b for k, (a, b) in mats.items()
                                 if k in first)
        if kind[0] == "attn":
            total += 3 * rows * causal_attn_flops(cfg, seq)
        else:
            total += 3 * rows * ssd_flops(cfg, seq)
        if kind[1] == "moe":
            total += expert_flops(layer, cfg["moe"]["top_k"], T)
    return total


def _all_dense(cfg):
    """The serving counts below know attention and dense MLP layers only:
    a configuration with an SSM or a MoE has other layers."""
    if cfg.get("ssm") or cfg.get("moe"):
        raise ValueError("serving counts are for dense attention layers")


def kv_bytes_per_token(cfg, itemsize: int = 4) -> int:
    """K and V of one position, all layers."""
    return 2 * cfg["num_layers"] * cfg["num_kv_heads"] * _hd(cfg) * itemsize


def decode_step(cfg, contexts, itemsize: int = 4):
    """(flops, bytes) of one decode step over live slots whose attention
    spans ``contexts`` positions each (the new token included)."""
    _all_dense(cfg)
    B = len(contexts)
    L = cfg["num_layers"]
    P = L * layer_params(cfg) + head_params(cfg)
    ctx = sum(contexts)
    flops = 2 * P * B + L * 4 * cfg["num_heads"] * _hd(cfg) * ctx
    kv = kv_bytes_per_token(cfg, itemsize)
    by = weight_bytes(cfg, itemsize) + B * cfg["d_model"] * itemsize \
        + kv * ctx + kv * B
    return flops, by


def prefill(cfg, seq: int, itemsize: int = 4):
    """(flops, bytes) of one prompt's prefill: its logits at the last
    position only, K/V written once."""
    _all_dense(cfg)
    L = cfg["num_layers"]
    flops = 2 * L * layer_params(cfg) * seq + 2 * head_params(cfg) \
        + L * causal_attn_flops(cfg, seq)
    by = weight_bytes(cfg, itemsize) + seq * cfg["d_model"] * itemsize \
        + kv_bytes_per_token(cfg, itemsize) * seq
    return flops, by


def least_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time a chip could take: the larger of compute and memory."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
