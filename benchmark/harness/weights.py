"""GPT-2 weights from the seed, made on the device in ONE jitted call.

The benchmark makes the weights and hands the same tree to the program and
to the plain reference, so the reference takes nothing the program made.
The tree has the layout the program's GPT-2 reads (stacked blocks, leading
axis = layer); that layout is the system's interface, stated here once.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

STD = 0.02
#: The sizes of a configuration's file that shape the model.
MODEL_KEYS = ("vocab_size", "n_positions", "n_layer", "n_embd", "n_head")


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """The model's sizes out of a configuration's file."""
    return {k: int(config[k]) for k in MODEL_KEYS}


def _shapes(cfg: Dict[str, int]) -> Dict[str, Any]:
    d, layers = cfg["n_embd"], cfg["n_layer"]
    return {
        "wte": (cfg["vocab_size"], d),
        "wpe": (cfg["n_positions"], d),
        "blocks": {
            "ln_1": {"scale": (layers, d), "bias": (layers, d)},
            "attn": {"qkv": {"w": (layers, d, 3 * d), "b": (layers, 3 * d)},
                     "proj": {"w": (layers, d, d), "b": (layers, d)}},
            "ln_2": {"scale": (layers, d), "bias": (layers, d)},
            "mlp": {"fc": {"w": (layers, d, 4 * d), "b": (layers, 4 * d)},
                    "proj": {"w": (layers, 4 * d, d), "b": (layers, d)}},
        },
        "ln_f": {"scale": (d,), "bias": (d,)},
    }


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key: jax.Array, sizes: Tuple[Tuple[str, int], ...]):
    cfg = dict(sizes)
    shapes = _shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, shape) in zip(keys, leaves):
        names = [p.key for p in path]
        if names[-1] == "scale":
            out.append(jnp.ones(shape, jnp.float32))
        elif names[-1] == "bias":
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            std = STD
            if names[-2:] == ["proj", "w"]:
                # GPT-2's scaled init of the two residual projections.
                std = STD / math.sqrt(2 * cfg["n_layer"])
            out.append(jax.random.normal(k, shape, jnp.float32) * std)
    return jax.tree_util.tree_unflatten(treedef, out)


def make(seed: int, cfg: Dict[str, int]) -> Dict[str, Any]:
    """f32 weights: N(0, 0.02) matrices, embeddings and dense biases
    (residual projections scaled by 1/sqrt(2L)); LayerNorm scale 1, bias 0.
    The same seed gives the same weights."""
    return _make(jax.random.PRNGKey(int(seed) % (1 << 63)),
                 tuple(sorted(sizes(cfg).items())))


def comparison_leaves(tree: Dict[str, Any]) -> List[Tuple[str, jax.Array]]:
    """The leaves the comparison is made on: every stacked block leaf split
    by layer, and the fused qkv split into q, k and v — so that a leaf whose
    gradient is nought by construction (a key's bias under softmax) stands
    alone and can be left out by the rule on the reference's gradient."""
    out: List[Tuple[str, jax.Array]] = []
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        names = [p.key for p in path]
        name = ".".join(names)
        if names[0] != "blocks":
            out.append((name, leaf))
            continue
        for layer in range(leaf.shape[0]):
            row = leaf[layer]
            if "qkv" in names:
                third = row.shape[-1] // 3
                for i, part in enumerate("qkv"):
                    out.append((f"{name}.{part}[{layer}]",
                                row[..., i * third:(i + 1) * third]))
            else:
                out.append((f"{name}[{layer}]", row))
    return out


@jax.jit
def leaf_norms(tree: Dict[str, Any]) -> jax.Array:
    """f32[n]: the norm of each comparison leaf, in ``comparison_leaves``
    order, in one call on the device."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
                      for _, leaf in comparison_leaves(tree)])
