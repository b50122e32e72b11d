"""Seeded weights and image embeddings, made on the device by the benchmark.

The program serves these arrays and the reference reads the same ones:
neither takes anything the other made.  Weights fill the program's
parameter tree (its shapes from ``jax.eval_shape``) in one jitted call, in
the dtype the configuration serves in, never leaf by leaf on the host.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02     # token embedding rows and stub image embedding rows
BIAS_STD = 0.02


def seed_key(seed: int, stream: int):
    """A JAX key from a seed of any size (seeds may exceed 32 bits)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def _fill(key, shapes, std_of):
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(leaves):
        name = _leaf_name(path)
        last = name.split("/")[-1]
        k = jax.random.fold_in(key, i)
        if name in std_of:                          # named by the arch file
            std = std_of[name]
        elif last == "scale":                       # RMSNorm scales
            out.append(jnp.ones(s.shape, s.dtype))
            continue
        elif last in ("bq", "bk", "bv"):
            std = BIAS_STD
        elif name == "embed":
            std = EMBED_STD
        else:                                       # (..., fan_in, fan_out)
            std = 1.0 / math.sqrt(s.shape[-2])
        out.append((jax.random.normal(k, s.shape, s.dtype) * std
                    ).astype(s.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def make_params(model, seed: int, std: Optional[Dict[str, float]] = None):
    """The program's parameter tree, filled from ``seed`` on the device.

    ``std`` (the ``STD`` of a configuration's architecture file) gives
    the standard deviation of named leaves, such as those the rules above
    do not cover; a name that is no leaf of the tree is an error."""
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    std = dict(std or {})
    names = {_leaf_name(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    unknown = sorted(set(std) - names)
    if unknown:
        raise ValueError(f"STD names no parameter: "
                         f"{', '.join(unknown)}; leaves: {sorted(names)}")
    return jax.jit(functools.partial(_fill, shapes=shapes, std_of=std))(
        seed_key(seed, 0))


@functools.lru_cache(maxsize=None)
def _image_fn(length: int, d_model: int, dtype):
    def f(key, index):
        k = jax.random.fold_in(key, index)
        return (jax.random.normal(k, (length, d_model), dtype)
                * EMBED_STD).astype(dtype)
    return jax.jit(f)


def image_embeds(seed: int, index: int, length: int, d_model: int,
                 dtype=jnp.bfloat16):
    """Stub vision-tower output of library image ``index``, on the device:
    normal(0, 0.02) rows, as the repo's stub frontend draws them."""
    return _image_fn(length, d_model, dtype)(seed_key(seed, 1),
                                             jnp.int32(index))
