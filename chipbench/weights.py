"""Weights made by the benchmark from ``--seed``, and their plain layout.

The program's parameter tree is read only for its structure and shapes
(``jax.eval_shape`` of the program's own initializer); every value is drawn
here, on the device, in one jitted call. Rules by leaf role:

* embedding table ``N(0, 0.02^2)``; linear weights (``w``, the expert
  stacks ``w_gate``/``w_up``/``w_down``), LoRA ``A`` and the Mamba-2 conv
  ``N(0, 1/d_in)`` with ``d_in = shape[-2]`` (``conv_dim`` for the conv);
  biases and the conv bias ``N(0, 0.02^2)``; norm scales
  ``1 + N(0, 0.1^2)``;
* Mamba-2 (arXiv:2405.21060): ``A_log = log U[1, 16]``, ``D = 1``,
  ``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform in the
  configuration's ``[dt_min, dt_max]``;
* LoRA ``B`` zero and ``s = alpha / rank`` (the adapter starts as a no-op);
* leaves under ``client`` of a training tree carry the client bank axis:
  one draw broadcast to every client (all clients start from one model).

Each leaf draws from ``fold_in(key, its flat index)``: its values depend
on its place in the tree and its rule alone.

``plain`` maps the program's tree onto the reference's layout: one flat
dict per layer, whatever its kind (attention and MLP weights under their
projection names, the others prefixed by their module), adapters keyed
``L<i>`` the same way.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LINEAR = ("w", "a", "w_gate", "w_up", "w_down", "conv_w")


def _key_names(path):
    return [getattr(k, "key", getattr(k, "idx", None)) for k in path]


def make(shapes, seed: int, alpha: float = 0.0, rank: int = 1,
         adapters: bool = False, ssm=None):
    """Random tree shaped like ``shapes`` (a pytree of ShapeDtypeStruct).
    ``adapters``: the tree is a split LoRA training tree (``base`` plus
    the adapter halves ``client``, a bank, and ``server``). ``ssm``: the
    configuration's Mamba-2 settings (``dt_min``, ``dt_max``), where the
    tree has Mamba-2 layers."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(key):
        out = []
        for i, (path, sd) in enumerate(flat):
            names = _key_names(path)
            k = jax.random.fold_in(key, i)
            shape = sd.shape
            bank = adapters and names[0] == "client"
            if bank:
                shape = shape[1:]
            leaf = names[-1]
            adapter = adapters and names[0] in ("client", "server")
            if leaf == "table":
                v = 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif leaf in LINEAR:
                v = jax.random.normal(k, shape, jnp.float32) \
                    / jnp.sqrt(jnp.float32(shape[-2]))
            elif leaf == "b" and adapter:
                v = jnp.zeros(shape, jnp.float32)
            elif leaf in ("b", "conv_b"):
                v = 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif leaf == "s":
                v = jnp.full(shape, alpha / rank, jnp.float32)
            elif leaf == "scale":
                v = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif leaf == "A_log":
                v = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1, 16))
            elif leaf == "D":
                v = jnp.ones(shape, jnp.float32)
            elif leaf == "dt_bias":
                lo, hi = math.log(ssm.dt_min), math.log(ssm.dt_max)
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
                v = dt + jnp.log(-jnp.expm1(-dt))
            else:
                raise ValueError(f"no rule for leaf {'/'.join(map(str, names))}")
            v = v.astype(sd.dtype)
            if bank:
                v = jnp.broadcast_to(v[None], sd.shape)
            out.append(v)
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.jit(fill)(key)


def _layers(groups):
    """Scan groups (list of stacked tuple-per-period trees) -> layer list."""
    out = []
    for period in groups:
        reps = jax.tree.leaves(period)[0].shape[0]
        for r in range(reps):
            for sub in period:
                out.append(jax.tree.map(lambda a, r=r: a[r], sub))
    return out


def _name(path):
    """A leaf's flat name in its layer's plain dict: attention and MLP
    weights go by their projection (``wq``, ``gate``, ``wq_b``), every other
    module's carry its name (``ssm_in_proj``, ``ssm_A_log``, ``moe_router``,
    ``moe_w_up``, ``moe_shared_down``); a linear's ``w`` and a norm's
    ``scale`` take their parent's name, a bias its parent's with ``_b``."""
    if path[0] in ("attn", "mlp"):
        path = path[1:]
    if path[-1] in ("w", "scale"):
        path = path[:-1]
    return "_".join(path)


def _flat(layer, adapter=False):
    """{flat name: leaf} of one layer; with ``adapter``, {flat name:
    {"a", "b"}} of its adapters (the scale ``s`` is ``alpha / rank``,
    which the reference takes from the configuration)."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(layer)[0]:
        names = _key_names(path)
        if adapter:
            *names, leaf = names
            if leaf == "s":
                continue
            out.setdefault(_name(names), {})[leaf] = v
            continue
        name = _name(names)
        assert name not in out, name
        out[name] = v
    return out


def plain(base):
    """The program's ``init_lm`` tree -> the reference's weights."""
    layers = [_flat(p) for p in
              _layers(base["client"]) + _layers(base["server"])]
    return {"embed": base["embed"]["table"], "layers": layers,
            "final_norm": base["final_norm"]["scale"],
            "head": base["head"]["w"]}


def plain_adapters(groups, first_layer: int, bank: bool):
    """Adapter groups -> {"L<i>": {proj: {"a", "b"}}}, every adapter of
    every layer kind; with ``bank`` the leaves keep their leading client
    axis."""
    if bank:
        # move the client axis behind the layer axis so layers slice first
        groups = jax.tree.map(lambda a: jnp.moveaxis(a, 0, 1), groups)
    layers = _layers(groups)
    return {f"L{first_layer + i}": _flat(p, adapter=True)
            for i, p in enumerate(layers)}
