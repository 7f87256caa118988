"""Weights made by the benchmark from ``--seed``, and their plain layout.

The program's parameter tree is read only for its structure and shapes
(``jax.eval_shape`` of the program's own initializer); every value is drawn
here, on the device, in one jitted call. Rules by leaf role:

* embedding table ``N(0, 0.02^2)``; linear weights and LoRA ``A``
  ``N(0, 1/d_in)``; biases ``N(0, 0.02^2)``; norm scales ``1 + N(0, 0.1^2)``;
* LoRA ``B`` zero and ``s = alpha / rank`` (the adapter starts as a no-op);
* leaves under ``client`` of a training tree carry the client bank axis:
  one draw broadcast to every client (all clients start from one model).

``plain`` maps the program's tree onto the reference's layout: one dict per
layer, adapters keyed ``L<i>`` by projection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PROJ = ("wq", "wk", "wv", "wo")
MLP = ("gate", "up", "down")


def _key_names(path):
    return [getattr(k, "key", getattr(k, "idx", None)) for k in path]


def make(shapes, seed: int, alpha: float = 0.0, rank: int = 1,
         adapters: bool = False):
    """Random tree shaped like ``shapes`` (a pytree of ShapeDtypeStruct).
    ``adapters``: the tree is a split LoRA training tree (``base`` plus
    the adapter halves ``client``, a bank, and ``server``)."""
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
            elif leaf in ("w", "a"):
                v = jax.random.normal(k, shape, jnp.float32) \
                    / jnp.sqrt(jnp.float32(shape[-2]))
            elif leaf == "b" and adapter:
                v = jnp.zeros(shape, jnp.float32)
            elif leaf == "b":
                v = 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif leaf == "s":
                v = jnp.full(shape, alpha / rank, jnp.float32)
            elif leaf == "scale":
                v = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
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


def _plain_layer(p):
    q = {"norm1": p["norm1"]["scale"], "norm2": p["norm2"]["scale"]}
    for name in PROJ:
        q[name] = p["attn"][name]["w"]
        if "b" in p["attn"][name]:
            q[name + "_b"] = p["attn"][name]["b"]
    for name in MLP:
        if name in p["mlp"]:
            q[name] = p["mlp"][name]["w"]
            if "b" in p["mlp"][name]:
                q[name + "_b"] = p["mlp"][name]["b"]
    return q


def plain(base):
    """The program's ``init_lm`` tree -> the reference's weights."""
    layers = [_plain_layer(p) for p in
              _layers(base["client"]) + _layers(base["server"])]
    return {"embed": base["embed"]["table"], "layers": layers,
            "final_norm": base["final_norm"]["scale"],
            "head": base["head"]["w"]}


def _plain_adapter(p):
    out = {}
    for name in PROJ:
        if name in p.get("attn", {}):
            out[name] = {"a": p["attn"][name]["a"], "b": p["attn"][name]["b"]}
    for name in MLP:
        if name in p.get("mlp", {}):
            out[name] = {"a": p["mlp"][name]["a"], "b": p["mlp"][name]["b"]}
    return out


def plain_adapters(groups, first_layer: int, bank: bool):
    """Adapter groups -> {"L<i>": {proj: {"a", "b"}}}; with ``bank`` the
    leaves keep their leading client axis."""
    if bank:
        # move the client axis behind the layer axis so layers slice first
        groups = jax.tree.map(lambda a: jnp.moveaxis(a, 0, 1), groups)
    layers = _layers(groups)
    return {f"L{first_layer + i}": _plain_adapter(p)
            for i, p in enumerate(layers)}
