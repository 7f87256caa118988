"""granite-4.0-h-micro on the split LoRA training path, on the CPU.

The program against ``reference/hybrid_split_lm.py`` on a tiny hybrid of
the cell's period and kinds (ten layers, attention at offset 5, Mamba-2 at
the others), with each Granite knob on its own and all together: the loss
of the checked steps, the first gradient and the change, compared as a
run compares them, under the cell's own limits; with a knob on its own,
the same readings against a reference that lacks it must fail. Every key
of the cell's configuration file reaches the program or is tied to what
it reads. Prefill then decode match the full forward on the tiny
preset."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run
from chipbench.drivers import train
from chipbench.tests import small
from chipbench.tools import calibrate
from repro.configs import get_config, reduced_config
from repro.configs.base import ModelConfig, SSMConfig
from repro.models import lm

WORKLOAD = "train-granite4hmicro-k4"
FILE = Path(__file__).parents[1] / "configs" / "granite-4.0-h-micro-l10.json"
# the Granite 4.0-H knobs, and the values that leave a model as it was
GRANITE = {"position_embedding_type": "nope", "embedding_multiplier": 12.0,
           "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
           "logits_scaling": 8.0}
PLAIN = {"position_embedding_type": "rope", "embedding_multiplier": 1.0,
         "residual_multiplier": 1.0, "attention_multiplier": None,
         "logits_scaling": 1.0}
# what the harness reads for itself, and the keys of config.json that the
# program has no use for: the family's name, the longest context (NoPE
# keeps no position table) and RoPE's scaling (there is none)
HARNESS = {"dtype", "cut", "boundary_codec"}
UNUSED = {"model_type", "max_position_embeddings", "rope_scaling"}


def published(cfg: ModelConfig) -> dict:
    """The program's configuration in the keys of the published
    ``config.json`` (granitemoehybrid), which the reference reads."""
    s = cfg.ssm
    return {
        "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": ["attention" if cfg.is_attn_layer(i) else "mamba"
                        for i in range(cfg.num_layers)],
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "intermediate_size": cfg.d_ff,
        "shared_intermediate_size": cfg.d_ff,
        "hidden_act": {"swiglu": "silu"}[cfg.mlp_act],
        "attention_bias": cfg.attn_bias,
        "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "mamba_d_state": s.state_dim,
        "mamba_d_conv": s.conv_dim,
        "mamba_expand": s.expand,
        "mamba_d_head": s.head_dim,
        "mamba_n_heads": s.expand * cfg.d_model // s.head_dim,
        "mamba_n_groups": s.n_groups,
        "mamba_chunk_size": s.chunk_size,
        "num_local_experts": 0 if cfg.moe is None else cfg.moe.num_experts,
        "num_experts_per_tok": 0 if cfg.moe is None else cfg.moe.top_k,
        # what the program's layers always are
        "mamba_conv_bias": True,
        "mamba_proj_bias": False,
        "normalization_function": "rmsnorm",
    }


def tiny(**knobs) -> dict:
    """A configuration file of the cell's kind at small widths: the
    published state size is cut to 32 and a chunk to 32 positions (two
    chunks a 64-token row)."""
    cfg = ModelConfig(
        name="tiny-granite4h", arch_type="hybrid", num_layers=10,
        d_model=256, num_heads=4, num_kv_heads=2, head_dim=64, d_ff=512,
        vocab_size=512, hybrid_period=10, hybrid_attn_offsets=(5,),
        ssm=SSMConfig(state_dim=32, conv_dim=4, expand=2, head_dim=64,
                      n_groups=1, chunk_size=32), **knobs)
    d = json.loads(json.dumps(dataclasses.asdict(cfg)))
    return dict(d, **published(cfg), dtype="float32", cut=1,
                boundary_codec="int8", reference="hybrid_split_lm")


TRAIN = dict(small.TRAIN, seq=64)


def _within(checks) -> bool:
    limits = run.resolve(WORKLOAD)["limits"]
    return run.compare(checks, limits)[1]


# ---------------------------------------------- program against reference
CASES = {"plain": {}, **{k: {k: v} for k, v in GRANITE.items()},
         "granite": GRANITE}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_matches_reference(case):
    knobs = CASES[case]
    cell = calibrate.cell_for(WORKLOAD, 2**31 + 77, 1.0)
    cell.config, cell.traffic = tiny(**knobs), TRAIN
    st = train.Setup(cell)
    prog = train.program_readings(st)
    st.drop()
    ref = train.reference_run(cell, st.base, st.snaps[0], st.fed, st.lr)
    checks = train.compare(prog, ref)
    assert _within(checks), checks
    if len(knobs) == 1:  # a reference without the knob reads past a limit
        cell.config = tiny()
        miss = train.reference_run(cell, st.base, st.snaps[0], st.fed,
                                   st.lr)
        assert not _within(train.compare(prog, miss))


def test_reference_ssd_is_the_recurrence():
    """The quadratic form against the SSM's own recurrence,
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t, y_t = C_t h_t (float32)."""
    from chipbench.reference import hybrid_split_lm as ref

    b, T, H, P, G, N = 2, 24, 4, 8, 2, 16
    ks = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(ks[0], (b, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    B = jax.random.normal(ks[3], (b, T, G, N))
    C = jax.random.normal(ks[4], (b, T, G, N))
    rep = H // G
    Bh, Ch = np.repeat(B, rep, 2), np.repeat(C, rep, 2)
    h = np.zeros((b, H, P, N))
    want = np.zeros((b, T, H, P))
    for t in range(T):
        decay = np.exp(np.asarray(dt[:, t] * A))[..., None, None]
        h = decay * h + np.einsum("bhp,bhn->bhpn",
                                  np.asarray(x[:, t] * dt[:, t, :, None]),
                                  Bh[:, t])
        want[:, t] = np.einsum("bhpn,bhn->bhp", h, Ch[:, t])
    with jax.default_matmul_precision("highest"):
        got = ref.ssd(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


# ------------------------------------------------- the configuration file
def test_every_key_reaches_the_program():
    cfgj = json.loads(FILE.read_text())
    cfg = train.model_config(cfgj)
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    pub = published(cfg)
    for k, v in cfgj.items():
        if k in set(train.DESCRIPTIVE) | HARNESS | UNUSED:
            continue
        if k == "ssm":
            assert set(v) <= {f.name for f in dataclasses.fields(SSMConfig)}
            assert {f: getattr(cfg.ssm, f) for f in v} == v
        elif k in fields:
            got = getattr(cfg, k)
            assert got == (tuple(v) if isinstance(v, list) else v), k
        else:
            assert k in pub, f"{k} reaches nothing in the program"
            assert pub[k] == v, k
    # the registry's preset, cut to the file's depth
    preset = get_config("granite-4.0-h-micro")
    assert cfg == dataclasses.replace(preset, name=cfg.name, source=cfg.source,
                                      num_layers=cfgj["num_layers"])
    assert {k: v for k, v in published(preset).items()
            if k not in ("num_hidden_layers", "layer_types")} == {
        k: cfgj[k] for k in pub if k not in ("num_hidden_layers",
                                             "layer_types")}
    assert {k: getattr(preset, k) for k in GRANITE} == GRANITE


# ----------------------------------------------------------- serving paths
@pytest.mark.parametrize("cut", [0, 1])
def test_prefill_then_decode_match_the_full_forward(cut):
    cfg = reduced_config(get_config("granite-4.0-h-micro"))
    assert {k: getattr(cfg, k) for k in GRANITE} == GRANITE
    plan = lm.build_plan(cfg, cut)
    params = lm.init_lm(jax.random.key(5), plan, jnp.float32)
    B, S, T = 2, 40, 46  # a ragged prompt (chunk 32), then 6 decode steps
    toks = jax.random.randint(jax.random.key(6), (B, T), 0, cfg.vocab_size)
    x, _ = lm.client_forward(params, plan, toks, remat=False,
                             dtype=jnp.float32)
    full, _ = lm.server_forward(params, plan, x, remat=False)
    logits, caches = lm.prefill(params, plan, toks[:, :S], max_len=T,
                                dtype=jnp.float32)
    got = [logits[:, 0]]
    for t in range(S, T):
        logits, caches = lm.decode_step(params, plan, toks[:, t:t + 1],
                                        caches, dtype=jnp.float32)
        got.append(logits[:, 0])
    np.testing.assert_allclose(np.stack(got, 1), np.asarray(full[:, S - 1:]),
                               rtol=2e-4, atol=2e-4)
