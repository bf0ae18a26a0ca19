"""Weights made by the benchmark from ``--seed``, for the program and for
the plain reference alike.

Every value sits on an exact grid: an integer in [-128, 127] from the
seeded random bits, times a power of two.  Such a value is exact in
bfloat16, so the weights the program is served in bf16 and the ones the
float32 reference computes with are the same numbers, and the reference
can make any one layer again on its own, bit for bit, without reading
anything the program holds.

A weight is named as the program's parameter tree names it, with the
segment prefix dropped (``attn.wq``, ``mlp.wo``, ``tok``): the reference
asks for the same names.  Layer ``l`` of a stacked weight comes from its
own key, so a vmapped stack and a single layer give the same values.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

# std of the token embedding (the program's own init uses 0.02)
EMBED_STD = 0.02


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64 bits of it."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def grid_step(std: float) -> float:
    """Power-of-two step of a uniform grid whose std is about ``std``
    (uniform on [-128, 127] steps has std 128 / sqrt(3) steps)."""
    return 2.0 ** round(math.log2(std * math.sqrt(3.0) / 128.0))


def _leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def std_of(name: str, shape) -> float | None:
    """Std of a weight by its name; None for a norm scale (all ones)."""
    if name.endswith("scale"):
        return None
    if name == "tok":
        return EMBED_STD
    return 1.0 / math.sqrt(int(shape[0]))  # fan-in is the first axis


def make(key: jax.Array, name: str, shape, dtype, layer=None) -> jax.Array:
    """One weight (one layer of it, where ``layer`` is given)."""
    shape = tuple(int(d) for d in shape)
    std = std_of(name, shape)
    if std is None:
        return jnp.ones(shape, dtype)
    k = _leaf_key(key, name)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    bits = jax.random.bits(k, shape, jnp.uint8)
    return ((bits.astype(jnp.int32) - 128).astype(jnp.float32)
            * grid_step(std)).astype(dtype)


def _canonical(path) -> tuple[str, bool]:
    """Program parameter path -> (weight name, stacked over layers)."""
    keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
    if keys[0] == "io":
        return ".".join(str(k) for k in keys[1:]), False
    if keys[0] == "dec":
        # ("dec", segment, "b<i>_<kind>", sub-block, ..., leaf)
        return ".".join(str(k) for k in keys[3:]), True
    raise ValueError(f"no rule for parameter {keys}")


def make_params(struct, seed: int):
    """The program's whole parameter tree, on the device, in one jitted
    call: ``struct`` is its abstract tree (``Model.abstract_init``)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(struct)

    def build(key):
        out = []
        for path, s in leaves:
            name, stacked = _canonical(path)
            if stacked:
                n_layers = s.shape[0]
                out.append(jax.vmap(
                    lambda l, name=name, s=s: make(
                        key, name, s.shape[1:], s.dtype, layer=l)
                )(jnp.arange(n_layers)))
            else:
                out.append(make(key, name, s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    params = jax.jit(build)(key_from_seed(seed))
    return jax.block_until_ready(params)
