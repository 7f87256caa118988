"""Operations and bytes that a step needs, from the configuration's sizes.

"Needs" is the algorithm's count, not what the program happens to compute:
causal attention counts its lower triangle, a frozen weight's gradient is
not needed, and a decode step reads each live slot's context once. So a
share of a peak built on these counts cannot pass 100 % unless the time
leaves out part of the work. ``tests/test_flops.py`` holds the counts
against XLA's own ``cost_analysis`` of the program's step.
"""
from __future__ import annotations


def _hd(cfg) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def layer_mats(cfg) -> dict:
    """(d_in, d_out) of each projection of one layer."""
    d, hd = cfg["d_model"], _hd(cfg)
    mats = {"wq": (d, cfg["num_heads"] * hd),
            "wk": (d, cfg["num_kv_heads"] * hd),
            "wv": (d, cfg["num_kv_heads"] * hd),
            "wo": (cfg["num_heads"] * hd, d),
            "up": (d, cfg["d_ff"]), "down": (cfg["d_ff"], d)}
    if cfg["mlp_act"] == "swiglu":
        mats["gate"] = (d, cfg["d_ff"])
    return mats


def layer_params(cfg) -> int:
    """Matmul weights of one layer."""
    return sum(a * b for a, b in layer_mats(cfg).values())


def head_params(cfg) -> int:
    return cfg["d_model"] * cfg["vocab_size"]


def weight_bytes(cfg, itemsize: int = 4) -> int:
    """Every weight a full forward reads, embedding rows aside."""
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


def train_step_flops(cfg, *, cohort: int, batch: int, seq: int,
                     rank: int) -> int:
    """A split LoRA step over ``cohort * batch`` sequences of ``seq``.

    Forward through every matmul and the head (2 per weight per token);
    backward needs activation gradients through every layer above the
    embedding (2 per weight per token), except the input gradient of the
    first layer's q/k/v projections (nothing below them trains); the
    adapters need their forward, input and weight gradients (6 per adapter
    weight per token); causal attention forward once and backward twice."""
    T = cohort * batch * seq
    L = cfg["num_layers"]
    mats = layer_mats(cfg)
    P = L * layer_params(cfg) + head_params(cfg)
    first_in = sum(a * b for k, (a, b) in mats.items()
                   if k in ("wq", "wk", "wv"))
    lora = L * sum(rank * (a + b) for a, b in mats.values())
    attn = 3 * L * cohort * batch * causal_attn_flops(cfg, seq)
    return 4 * P * T - 2 * first_in * T + 6 * lora * T + attn


def kv_bytes_per_token(cfg, itemsize: int = 4) -> int:
    """K and V of one position, all layers."""
    return 2 * cfg["num_layers"] * cfg["num_kv_heads"] * _hd(cfg) * itemsize


def decode_step(cfg, contexts, itemsize: int = 4):
    """(flops, bytes) of one decode step over live slots whose attention
    spans ``contexts`` positions each (the new token included)."""
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
    L = cfg["num_layers"]
    flops = 2 * L * layer_params(cfg) * seq + 2 * head_params(cfg) \
        + L * causal_attn_flops(cfg, seq)
    by = weight_bytes(cfg, itemsize) + seq * cfg["d_model"] * itemsize \
        + kv_bytes_per_token(cfg, itemsize) * seq
    return flops, by


def least_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time a chip could take: the larger of compute and memory."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
