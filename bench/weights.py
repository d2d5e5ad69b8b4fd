"""Model weights made from ``--seed`` by the benchmark, not by the program.

Each parameter is named by its path in the program's parameter tree
(``embed``, ``stack/0/attn/wq``, ``final_norm/scale``, ...).  Element
``i`` of a leaf is a hash of (seed, path, layer, i), so the whole tree is
made on the device in one jitted call, at the speed of writing it, by one
program for every seed (the seed is an argument, not a constant), and
the reference regenerates any leaf or any layer of a stacked leaf on its
own, bit for bit, without taking anything from the program.

Matrices are uniform with standard deviation 1/sqrt(fan_in), the embedding
uniform with standard deviation 0.02, norm scales one and biases zero.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

M32 = 0xFFFFFFFF


def _mix(x):
    """murmur3's 32-bit finalizer."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as three 32-bit words: an argument of the
    programs that make weights, so one compiled program serves every
    seed."""
    return np.array([(seed >> s) & M32 for s in (0, 32, 64)], np.uint32)


def _salt(words, path: str):
    """A 32-bit salt from the seed's words and a leaf's path."""
    h = jnp.uint32(zlib.crc32(path.encode()))
    for i in range(3):
        h = _mix(h ^ _mix(words[i] + jnp.uint32(0x9E3779B9 * (i + 1) & M32)))
    return h


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _uniform(salt, layer, shape):
    """Uniform in [-1, 1) for each element of one (layer of a) leaf."""
    n = math.prod(shape)
    i = jnp.arange(n, dtype=jnp.uint32)
    k = _mix(salt ^ _mix(jnp.asarray(layer, jnp.uint32) + jnp.uint32(0x9E3779B9)))
    x = _mix(i * jnp.uint32(0x9E3779B1) + k)
    u = (x >> 8).astype(jnp.float32) * (2.0 ** -23) - 1.0
    return u.reshape(shape)


def leaf_value(words, path: str, shape, layer=0, dtype=jnp.float32):
    """One leaf (one layer's slice of a leaf stacked over layers); ``words``
    from ``seed_words``."""
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "bias":
        return jnp.zeros(shape, dtype)
    std = 0.02 if name == "embed" else 1.0 / math.sqrt(shape[-2])
    u = _uniform(_salt(words, path), layer, tuple(shape))
    return (u * np.float32(std * math.sqrt(3.0))).astype(dtype)


def leaf(seed: int, path: str, shape, layer=0, dtype=jnp.float32):
    """Regenerate one leaf, or layer ``layer`` of a leaf stacked over
    layers, on its own."""
    return _leaf_jit(path, tuple(shape), jnp.dtype(dtype))(seed_words(seed), layer)


@functools.lru_cache(maxsize=None)
def _leaf_jit(path, shape, dtype):
    return jax.jit(lambda words, l: leaf_value(words, path, shape, l, dtype))


def make(shape_tree, seed: int, out_shardings=None):
    """The whole parameter tree on the device, in one jitted call.

    ``shape_tree`` is the program's layout (``jax.eval_shape`` of its
    initializer).  Leaves under ``stack`` carry the layer axis first."""

    def build(words):
        def one(path, sds):
            p = _path_str(path)
            if p.startswith("stack/"):
                return jax.vmap(lambda l: leaf_value(words, p, sds.shape[1:], l, sds.dtype))(
                    jnp.arange(sds.shape[0], dtype=jnp.uint32))
            return leaf_value(words, p, sds.shape, 0, sds.dtype)

        return jax.tree_util.tree_map_with_path(one, shape_tree)

    return jax.jit(build, out_shardings=out_shardings)(seed_words(seed))
