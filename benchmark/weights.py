"""Weights from the seed: one jitted call, on the device, in the type served.

The benchmark makes the weights, hands them to the program, and makes them
again for the plain reference once the program's state is freed. Matrices
are normal with the published `initializer_range`; norm scales are ones.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def key_of(seed: int):
    # --seed may pass 2**31: fold the high bits in instead of overflowing
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaves(shapes, key, std):
    """shapes: {name: (shape, dtype)} -> {name: array}; names sorted, so
    that a leaf's numbers depend on its name's rank and the seed alone."""
    out = {}
    for i, name in enumerate(sorted(shapes)):
        shape, dtype = shapes[name]
        if len(shape) == 1 or name.rsplit("/", 1)[-1].startswith("ln_"):
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                           jnp.float32) * std).astype(dtype)
    return out


def train_shapes(model: dict):
    """The stacked tree HybridTrainer trains (models/llama.py layout)."""
    h, f, v = model["hidden_size"], model["intermediate_size"], \
        model["vocab_size"]
    n_layers = model["num_hidden_layers"]
    kv = model["num_key_value_heads"] * (h // model["num_attention_heads"])
    dt = jnp.bfloat16 if model["torch_dtype"] == "bfloat16" else jnp.float32
    blocks = {"wq": (h, h), "wk": (h, kv), "wv": (h, kv), "wo": (h, h),
              "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)}
    shapes = {f"blocks/{k}": ((n_layers,) + s, dt) for k, s in blocks.items()}
    shapes["blocks/ln_attn"] = ((n_layers, h), jnp.float32)
    shapes["blocks/ln_mlp"] = ((n_layers, h), jnp.float32)
    shapes["embed"] = ((v, h), dt)
    shapes["lm_head"] = ((h, v), dt)
    shapes["final_norm"] = ((h,), jnp.float32)
    return shapes


def unflatten(flat: dict) -> dict:
    tree: dict = {}
    for name, a in flat.items():
        node = tree
        *parents, leaf = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = a
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    flat = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            flat.update(flatten(v, f"{prefix}{k}/"))
        else:
            flat[prefix + k] = v
    return flat


def make_train_params(model: dict, seed: int, shardings=None):
    """The trainer's parameter tree, made in one jitted call."""
    shapes = train_shapes(model)
    std = model["initializer_range"]
    fn = jax.jit(lambda key: unflatten(leaves(shapes, key, std)),
                 out_shardings=shardings)
    return fn(key_of(seed))


def make_like(arrays: dict, model: dict, seed: int, donate: bool):
    """{name: array} with each given array's shape and dtype, for a model
    whose parameters are already laid out (the serving model). `arrays` is
    {name: array}, or {name: (shape, dtype)} where nothing is donated.
    With `donate` the old buffers are given to the call, so that nothing
    is held twice."""
    shapes = {k: a if isinstance(a, tuple) else (a.shape, a.dtype)
              for k, a in arrays.items()}
    std = model["initializer_range"]
    fn = jax.jit(lambda old, key: leaves(shapes, key, std),
                 donate_argnums=(0,) if donate else (), keep_unused=True)
    return fn(arrays if donate else None, key_of(seed))
