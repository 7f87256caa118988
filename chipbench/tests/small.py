"""Small configurations and mixes for running the harness on the CPU."""

CONFIG = {
    "name": "tiny", "arch_type": "dense", "num_layers": 3, "d_model": 256, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 64, "d_ff": 512, "vocab_size": 512,
    "rope_theta": 10000.0, "mlp_act": "swiglu", "attn_bias": False,
    "mlp_bias": False, "norm_eps": 1e-5, "tie_embeddings": False,
    "dtype": "float32", "cut": 1, "boundary_codec": "int8",
    "reference": "dense_split_lm"}

CONFIG_GELU = dict(CONFIG, name="tiny-gelu", num_kv_heads=1, mlp_act="gelu",
                   attn_bias=True, mlp_bias=True)

# Mamba-2 mixers (the published state size, 128) with one attention layer
# in a period of 4, and a MoE FFN (4 experts, top 2, one shared expert) in
# every layer; no reference covers it yet, so only the driver's Setup runs it
CONFIG_HYBRID = dict(
    CONFIG, name="tiny-hybrid-moe", arch_type="hybrid", num_layers=4,
    hybrid_period=4, hybrid_attn_offsets=[2],
    ssm={"state_dim": 128, "conv_dim": 4, "expand": 2, "head_dim": 64,
         "n_groups": 1, "chunk_size": 32, "dt_min": 0.001, "dt_max": 0.1},
    moe={"num_experts": 4, "top_k": 2, "d_ff_expert": 128,
         "num_shared_experts": 1})

TRAIN = {"driver": "train", "scheme": "sfl_ga", "peft": "lora",
         "lora_rank": 4, "lora_alpha": 8.0, "clients": 6, "cohort": 2,
         "sampler": "uniform", "batch": 1, "seq": 32, "optimizer": "sgd",
         "lr": 0.05, "check_steps": 3}

_LAWS = {"prompt": {"law": "lognormal", "median": 20, "sigma": 0.9,
                    "buckets": [16, 32]},
         "output": {"law": "lognormal", "median": 6, "sigma": 0.8,
                    "min": 3, "max": 12}}

ONLINE = dict(_LAWS, driver="serve", slots=4, page_size=8, max_len=48,
              warmup_s=0.5, arrivals={"kind": "poisson", "rate": 20.0},
              check={"requests": 3})

OFFLINE = dict(_LAWS, driver="serve", slots=4, page_size=8, max_len=48,
               warmup_s=0.5, arrivals={"kind": "backlog", "count": 200},
               check={"requests": 3})

# the controls' check: a wide vocabulary and hundreds of served tokens, so
# that the comparison has as many near-ties to read as at the cells' size
CONFIG_CHECK = dict(CONFIG, name="tiny-check", vocab_size=8192)
CHECK = {"prompt": {"law": "lognormal", "median": 20, "sigma": 0.9,
                    "buckets": [16, 32]},
         "output": {"law": "lognormal", "median": 24, "sigma": 0.5,
                    "min": 8, "max": 60},
         "driver": "serve", "slots": 8, "page_size": 8, "max_len": 96,
         "warmup_s": 0.3, "arrivals": {"kind": "backlog", "count": 400},
         "check": {"requests": 16}}
