"""The harness takes every decoder family the program builds: dense,
MoE and hybrid Mamba-2 presets of ``repro.configs`` (reduced), read from
JSON, drawn, laid out for the reference and counted, on the CPU. The
dense path's numbers are held to the integers and bits they had before
the harness learned the other kinds."""
import dataclasses
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, weights
from chipbench.drivers import train
from chipbench.tests import small, test_flops
from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.configs.base import PeftSpec
from repro.core import algorithms as alg
from repro.models import lm
from repro.models import transformer as tf

CUT, CLIENTS, RANK, ALPHA = 1, 3, 4, 8.0
ALL_TARGETS = ("attn", "mlp", "ssm", "router")
DECODERS = {a: reduced_config(get_config(a)) for a in ARCH_IDS
            if get_config(a).encoder is None}


def _lora_builds(cfg) -> bool:
    try:
        jax.eval_shape(lambda k: lm.init_lm_loras(k, _plan(cfg)),
                       jax.random.key(0))
    except ZeroDivisionError:
        # mamba2-130m: the program's lora_target_dims reads the attention
        # head size of a model with no attention heads
        return False
    return True


def _plan(cfg, targets=PeftSpec.targets):
    return lm.build_plan(cfg, CUT, peft=PeftSpec("lora", RANK, ALPHA,
                                                 targets))


LORA = sorted(a for a, c in DECODERS.items() if _lora_builds(c))


def _json(cfg) -> dict:
    """The preset as a configuration file holds it, with the harness's
    keys."""
    d = json.loads(json.dumps(dataclasses.asdict(cfg)))
    return dict(d, cut=CUT, dtype="float32", boundary_codec="int8",
                reference="dense_split_lm", assumed=["x"], reduced=[],
                published={"num_layers": 9}, deployment="x")


def _split_shapes(cfg, targets=PeftSpec.targets):
    plan = _plan(cfg, targets)
    return jax.eval_shape(lambda k: alg.split_lm_lora_params(
        lm.init_lm(k, plan), lm.init_lm_loras(k, plan), CLIENTS),
        jax.random.key(0))


def _base_shapes(cfg):
    return jax.eval_shape(lambda k: lm.init_lm(k, lm.build_plan(cfg, CUT)),
                          jax.random.key(0))


def _numbered(shapes):
    """A tree shaped like ``shapes`` whose elements are 0, 1, 2, ...: each
    value names the one element it came from."""
    leaves, treedef = jax.tree.flatten(shapes)
    out, n = [], 0
    for sd in leaves:
        out.append(np.arange(n, n + sd.size, dtype=np.float64)
                   .reshape(sd.shape))
        n += sd.size
    return jax.tree.unflatten(treedef, out), n


def _values(tree) -> np.ndarray:
    return np.sort(np.concatenate([np.ravel(v) for v in jax.tree.leaves(tree)]))


# ------------------------------------------------------------ every family
@pytest.mark.parametrize("arch", sorted(DECODERS))
def test_model_config_from_json_is_the_preset(arch):
    cfg = DECODERS[arch]
    got = train.model_config(_json(cfg))
    assert got == cfg
    assert hash(got) == hash(cfg)


@pytest.mark.parametrize("arch", sorted(DECODERS))
def test_reference_config_carries_every_architecture_key(arch):
    cfgj = _json(DECODERS[arch])
    rc = train.reference_config(cfgj, dict(small.TRAIN, page_size=8))
    assert rc["ssm"] == cfgj["ssm"] and rc["moe"] == cfgj["moe"]
    assert rc["hybrid_attn_offsets"] == cfgj["hybrid_attn_offsets"]
    assert not set(rc) & set(train.DESCRIPTIVE)
    assert set(rc) == set(cfgj) - set(train.DESCRIPTIVE) | {
        "lora_rank", "lora_alpha", "page_size"}
    assert (rc["lora_rank"], rc["lora_alpha"], rc["page_size"]) == (4, 8.0, 8)


@pytest.mark.parametrize("arch", sorted(DECODERS))
def test_weights_rule_for_every_base_leaf(arch):
    cfg = DECODERS[arch]
    p = weights.make(_base_shapes(cfg), 2**33 + 7, ssm=cfg.ssm)
    assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(p))
    for path, v in jax.tree_util.tree_flatten_with_path(p)[0]:
        leaf = weights._key_names(path)[-1]
        if leaf == "D":
            assert bool((v == 1).all())
        elif leaf == "A_log":
            assert bool((v >= 0).all() & (v <= np.log(16.0)).all())
        elif leaf == "dt_bias":
            dt = jax.nn.softplus(v)
            assert bool((dt >= 0.99 * cfg.ssm.dt_min).all()
                        & (dt <= 1.01 * cfg.ssm.dt_max).all())


@pytest.mark.parametrize("arch", LORA)
def test_weights_rule_for_every_split_lora_leaf(arch):
    cfg = DECODERS[arch]
    p = weights.make(_split_shapes(cfg, ALL_TARGETS), 2**33 + 7, ALPHA, RANK,
                     adapters=True, ssm=cfg.ssm)
    for path, v in jax.tree_util.tree_flatten_with_path(p)[0]:
        names = weights._key_names(path)
        if names[0] == "client":  # one draw broadcast to every client
            assert bool((v == v[:1]).all())
        if names[0] != "base" and names[-1] == "b":
            assert bool((v == 0).all())
        if names[-1] == "s":
            assert bool((v == ALPHA / RANK).all())


@pytest.mark.parametrize("arch", sorted(DECODERS))
def test_plain_holds_every_element_once(arch):
    tree, n = _numbered(_base_shapes(DECODERS[arch]))
    np.testing.assert_array_equal(_values(weights.plain(tree)), np.arange(n))


@pytest.mark.parametrize("arch", LORA)
def test_plain_adapters_hold_every_adapter_once(arch):
    cfg = DECODERS[arch]
    shapes = _split_shapes(cfg, ALL_TARGETS)
    tree, _ = _numbered({k: shapes[k] for k in ("client", "server")})
    adapters = jax.tree_util.tree_flatten_with_path(tree)[0]
    ab = [v for path, v in adapters
          if weights._key_names(path)[-1] in ("a", "b")]
    plain = train._plain_trainable(tree, CUT)
    np.testing.assert_array_equal(_values(plain), _values(ab))
    # every targeted projection of every layer, each kind under its name
    names = {k for layer in plain["client"].values() for k in layer} | {
        k for layer in plain["server"].values() for k in layer}
    kinds = set(tf.layer_specs(cfg))
    if any(m == "ssm" for m, _ in kinds):
        assert {"ssm_in_proj", "ssm_out_proj"} <= names
    if any(f == "moe" for _, f in kinds):
        assert "moe_router" in names
    assert len(plain["client"]) + len(plain["server"]) == cfg.num_layers


# ------------------------------------------------------------ FLOP counts
@pytest.mark.parametrize("arch", LORA)
def test_lora_count_is_the_adapter_tree(arch):
    cfg = DECODERS[arch]
    shapes = _split_shapes(cfg)
    n = sum(v.size for path, v in
            jax.tree_util.tree_flatten_with_path(shapes["server"])[0]
            if weights._key_names(path)[-1] != "s")
    n += sum(v.size // CLIENTS for path, v in
             jax.tree_util.tree_flatten_with_path(shapes["client"])[0]
             if weights._key_names(path)[-1] != "s")
    cfgj = _json(cfg)
    assert sum(flops.layer_lora(cfgj, k, RANK)
               for k in tf.layer_specs(cfg)) == n


# The needed count sits under XLA's count of the program's compiled step;
# how far under depends on what the program computes beyond the need.
BAND = {
    # full attention squares (the need is the causal triangle), softmax,
    # norms, the int8 codec and the loss
    "dense": 0.85,
    # as dense, and every expert's capacity slots: the program computes
    # capacity_factor (1.25) times top_k slots a token, the need top_k
    "moe": 0.75,
    # as moe, and the SSD per head over full chunk squares with its decays
    # (the need: C B^T per group, the causal triangle)
    "hybrid": 0.78,
}
FAMILY = {"dense": "dense", "vlm": "dense", "moe": "moe", "hybrid": "hybrid"}


STEPS = dict({a: DECODERS[a] for a in LORA},
             **{"tiny-hybrid-moe": train.model_config(small.CONFIG_HYBRID)})


@pytest.mark.parametrize("arch", sorted(STEPS))
def test_train_step_flops_within_xla_count(arch):
    # split at 1, the rest in one scan group: every scan runs one trip,
    # which XLA counts
    cfg = STEPS[arch]
    K, b, S = 2, 1, 128
    xla = test_flops.xla_step_flops(cfg, K, b, S, RANK)
    need = flops.train_step_flops(_json(cfg), test_flops.plain_layers(cfg),
                                  kinds=tf.layer_specs(cfg), cohort=K,
                                  batch=b, seq=S, rank=RANK)
    assert BAND[FAMILY[cfg.arch_type]] * xla <= need <= xla


# ------------------------------------------------ the experts a layer holds
MOE = sorted(a for a, c in STEPS.items() if c.moe is not None)
EXPERTS = ("moe_w_gate", "moe_w_up", "moe_w_down")
T = 2 * 1 * 128  # tokens of the step counted below


def _moe(arch):
    """(configuration file, layer kinds, plain layer shapes) of a preset."""
    cfg = STEPS[arch]
    return _json(cfg), tf.layer_specs(cfg), test_flops.plain_layers(cfg)


def _count(cfgj, kinds, layers):
    return flops.train_step_flops(cfgj, layers, kinds=kinds, cohort=2,
                                  batch=1, seq=128, rank=RANK)


def _hold(layers, lo, hi):
    """Each expert layer holding experts ``lo`` to ``hi - 1`` of its
    stacks: their expert axis sliced, the router left whole."""
    return jax.eval_shape(lambda ls: [
        {k: v[lo:hi] if k in EXPERTS else v for k, v in layer.items()}
        for layer in ls], layers)


def _experts(layers, held, f, routed, shared):
    """Each expert layer with ``held`` experts of width ``f``, a router
    of width ``routed`` and a shared expert of width ``shared`` (0:
    none)."""
    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    out = []
    for layer in layers:
        if "moe_router" in layer:
            d = layer["moe_router"].shape[0]
            layer = {k: v for k, v in layer.items()
                     if not k.startswith("moe_")}
            layer.update(moe_router=sd(d, routed), moe_w_gate=sd(held, d, f),
                         moe_w_up=sd(held, d, f), moe_w_down=sd(held, f, d))
            if shared:
                layer.update(moe_shared_gate=sd(d, shared),
                             moe_shared_up=sd(d, shared),
                             moe_shared_down=sd(shared, d))
        out.append(layer)
    return out


def _moe_layers(kinds):
    return sum(ffn == "moe" for _, ffn in kinds)


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("arch", MOE)
def test_held_share_scales_only_the_routed_experts(arch, n):
    cfgj, kinds, layers = _moe(arch)
    m = cfgj["moe"]
    routed = _moe_layers(kinds) * 4 * T * m["top_k"] * 3 * cfgj["d_model"] \
        * m["d_ff_expert"]
    part = _hold(layers, 0, m["num_experts"] // n)
    assert _count(cfgj, kinds, layers) - _count(cfgj, kinds, part) \
        == routed - routed // n


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("arch", MOE)
def test_held_shares_sum_to_the_whole_layer(arch, n):
    # what every chip computes alike (mixers, router, shared expert, head,
    # adapters) once, and each share of the routed experts
    cfgj, kinds, layers = _moe(arch)
    E = cfgj["moe"]["num_experts"]
    shares = [_count(cfgj, kinds, _hold(layers, i * E // n, (i + 1) * E // n))
              for i in range(n)]
    alike = _count(cfgj, kinds, _hold(layers, 0, 0))
    assert sum(shares) - (n - 1) * alike == _count(cfgj, kinds, layers)


@pytest.mark.parametrize("arch", MOE)
def test_router_width_is_its_output_axis(arch):
    # granite-4.0-h-small cut to one chip: 9 of 72 experts held, the router
    # at its full width, while ``num_experts`` holds the held count
    cfgj, kinds, layers = _moe(arch)
    cfgj = dict(cfgj, moe=dict(cfgj["moe"], num_experts=9))
    d, f, k = cfgj["d_model"], cfgj["moe"]["d_ff_expert"], cfgj["moe"]["top_k"]
    n = _moe_layers(kinds)
    held = _count(cfgj, kinds, _experts(layers, 9, f, 72, 0))
    none = _count(cfgj, kinds, _experts(layers, 0, f, 72, 0))
    narrow = _count(cfgj, kinds, _experts(layers, 0, f, 9, 0))
    assert held - none == n * 4 * T * k * 3 * d * f * 9 // 72
    assert none - narrow == n * 4 * T * d * (72 - 9)


@pytest.mark.parametrize("arch", MOE)
def test_shared_width_is_its_output_axis(arch):
    # granite-4.0-h-small's shared expert: 1536 wide beside experts of 768
    cfgj, kinds, layers = _moe(arch)
    cfgj = dict(cfgj, moe=dict(cfgj["moe"], d_ff_expert=768,
                               num_shared_experts=1))
    E = cfgj["moe"]["num_experts"]
    wide = _count(cfgj, kinds, _experts(layers, E, 768, E, 1536))
    none = _count(cfgj, kinds, _experts(layers, E, 768, E, 0))
    assert wide - none == _moe_layers(kinds) * 4 * T * 3 * cfgj["d_model"] \
        * 1536


@pytest.mark.parametrize("key,value", [
    ("num_experts", 9), ("d_ff_expert", 768), ("num_shared_experts", 2)])
@pytest.mark.parametrize("arch", MOE)
def test_tree_decides_the_expert_widths(arch, key, value):
    cfgj, kinds, layers = _moe(arch)
    changed = dict(cfgj, moe=dict(cfgj["moe"], **{key: value}))
    assert _count(changed, kinds, layers) == _count(cfgj, kinds, layers)


def test_expert_stacks_must_agree():
    cfgj, kinds, layers = _moe("tiny-hybrid-moe")
    torn = [dict(layer, moe_w_gate=jax.eval_shape(lambda v: v[:1],
                                                  layer["moe_w_gate"]))
            for layer in layers]
    with pytest.raises(ValueError):
        _count(cfgj, kinds, torn)


@pytest.mark.parametrize("groups,band", [(4, 0.55), (1, 0.45)])
def test_ssd_flops_within_xla_count(groups, band):
    """The program's chunked SSD forward alone, on 4 heads: it computes
    each chunk's full square where the need is its causal triangle, and
    the decays' exponentials; with one group it also repeats ``B`` and
    ``C`` for every head, where the need takes ``C B^T`` once a group."""
    from repro.models import ssm

    s, h, p, n, q = 256, 4, 32, 32, 64
    sd = jax.ShapeDtypeStruct
    xla = test_flops._xla_flops(jax.jit(
        lambda *a: ssm.ssd_chunked(*a, q)[0]).lower(
        sd((1, s, h, p), jnp.float32), sd((1, s, h), jnp.float32),
        sd((h,), jnp.float32), sd((1, s, groups, n), jnp.float32),
        sd((1, s, groups, n), jnp.float32)).compile())
    cfgj = {"d_model": h * p // 2, "ssm": {
        "expand": 2, "head_dim": p, "n_groups": groups, "state_dim": n,
        "chunk_size": q}}
    assert band * xla <= flops.ssd_flops(cfgj, s) <= xla


@pytest.mark.parametrize("arch", sorted(DECODERS))
def test_serving_counts_refuse_other_kinds(arch):
    cfg = DECODERS[arch]
    cfgj = _json(cfg)
    if all(k == flops.DENSE for k in tf.layer_specs(cfg)):
        assert flops.decode_step(cfgj, [16])[0] > 0
        assert flops.prefill(cfgj, 16)[0] > 0
        return
    with pytest.raises(ValueError):
        flops.decode_step(cfgj, [16])
    with pytest.raises(ValueError):
        flops.prefill(cfgj, 16)


# ------------------------------------------------- the driver on a hybrid
def test_setup_trains_a_hybrid_moe():
    from chipbench.tools import calibrate

    cell = calibrate.cell_for("train-granite8b-k4", 2**31 + 99, 1.0)
    cell.config, cell.traffic = small.CONFIG_HYBRID, small.TRAIN
    st = train.Setup(cell)
    assert len(st.losses) == small.TRAIN["check_steps"]
    assert all(np.isfinite(st.losses))
    assert st.flops > 0
    # every adapter, the Mamba-2 mixers' with them, has moved off zero
    last = train._plain_trainable(st.snaps[-1], small.CONFIG_HYBRID["cut"])
    moved = {f"{layer}.{proj}": bool(np.any(ad["b"] != 0))
             for half in last.values() for layer, lay in half.items()
             for proj, ad in lay.items()}
    assert any(k.endswith("ssm_in_proj") for k in moved)
    assert all(moved.values()), moved


# ---------------------------------------------------- the dense path held
def test_dense_counts_are_unchanged():
    cfgj = json.loads((Path(__file__).parents[1] / "configs" /
                       "granite-8b-l4.json").read_text())
    cfg = train.model_config(cfgj)
    assert flops.train_step_flops(cfgj, test_flops.plain_layers(cfg),
                                  kinds=tf.layer_specs(cfg), cohort=4,
                                  batch=1, seq=512, rank=8) == 8828305276928
    assert flops.layer_params(cfgj) == 218103808
    assert flops.weight_bytes(cfgj) == 4295114752


def test_hybrid_cell_count_is_unchanged():
    cfgj = json.loads((Path(__file__).parents[1] / "configs" /
                       "granite-4.0-h-micro-l10.json").read_text())
    cfg = train.model_config(cfgj)
    assert flops.train_step_flops(cfgj, test_flops.plain_layers(cfg),
                                  kinds=tf.layer_specs(cfg), cohort=4,
                                  batch=1, seq=512, rank=8) == 7960191303680


@pytest.mark.parametrize("config,digest", [
    (small.CONFIG,
     "a5ca9017c0170269c47885f244b26fc7cb6cebfdd15ba6dcf100c9cd7bd8ade4"),
    (small.CONFIG_GELU,
     "11e9d1ab52b6d0c7c73ceab8b7f7a857892530816d1d4b754acb5b256049f5cc"),
], ids=["tiny", "tiny-gelu"])
def test_dense_weights_are_unchanged(config, digest):
    cfg = train.model_config(config)
    plan = lm.build_plan(cfg, config["cut"], peft=PeftSpec("lora", 4, 8.0))
    shapes = jax.eval_shape(lambda k: alg.split_lm_lora_params(
        lm.init_lm(k, plan), lm.init_lm_loras(k, plan), 6), jax.random.key(0))
    p = weights.make(shapes, 2**33 + 12345, 8.0, 4, adapters=True)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == digest
    plain = weights.plain(p["base"])
    keys = {"norm1", "norm2", "wq", "wk", "wv", "wo", "up", "down"}
    if config["mlp_act"] == "swiglu":
        keys.add("gate")
    if config["attn_bias"]:
        keys |= {"wq_b", "wk_b", "wv_b", "up_b", "down_b"}
    assert sorted(plain) == ["embed", "final_norm", "head", "layers"]
    assert all(set(layer) == keys for layer in plain["layers"])
    adapters = weights.plain_adapters(p["client"]["groups"], 0, True)
    assert {k: set(v) for k, v in adapters.items()} == {
        "L0": keys - {"norm1", "norm2", "wq_b", "wk_b", "wv_b", "up_b",
                      "down_b"}}
