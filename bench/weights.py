"""Weights made from the seed, exactly, for the program and the reference.

Every entry is an integer drawn by ``jax.random.bits`` times a power of
two, so its float32 bits are the same whatever program computes it: the
program gets its whole parameter tree from one jitted call, and the
reference draws the same matrices again one layer at a time and finds
them bit for bit. (Normal draws scaled by ``1/sqrt(fan_in)`` are not
like that: XLA fuses the scaling into the draw differently per program,
and a binarized network turns one ulp of a weight scale into other
tokens.) Entries are uniform on ``[-a, a)`` with ``a`` the power of two
nearest ``sqrt(3 / fan_in)``, about the spread of the usual
``N(0, 1/fan_in)`` initialization.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
PROJECTIONS = ("q", "k", "v", "o", "w1", "w3", "w2")


def seed_key(seed: int) -> jax.Array:
    """A JAX key from a seed of up to 64 bits (``jax.random.key`` keeps
    only the low 32 without x64)."""
    seed %= 2**64
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def uniform(key, shape, fan_in: int) -> jax.Array:
    """Exact uniform entries: 24 random bits, centred, times 2**-k."""
    e = round(math.log2(math.sqrt(3.0 / fan_in)))
    v = (jax.random.bits(key, shape, jnp.uint32) >> 8).astype(jnp.int32) - (1 << 23)
    return v.astype(F32) * (2.0 ** (e - 23))


def shapes(dims) -> dict[str, tuple[int, int]]:
    """(fan_in, fan_out) of each binarized projection of one layer."""
    d, hd = dims.d, dims.head_dim
    return {
        "q": (d, dims.heads * hd),
        "k": (d, dims.kv_heads * hd),
        "v": (d, dims.kv_heads * hd),
        "o": (dims.heads * hd, d),
        "w1": (d, dims.d_ff),
        "w3": (d, dims.d_ff),
        "w2": (dims.d_ff, d),
    }


def table_keys(seed: int):
    """Keys of the embedding, the untied head, and the layers."""
    return jax.random.split(seed_key(seed), 3)


def projection(layer_key, name: str, dims) -> jax.Array:
    """One layer's float32 weight ``name`` from that layer's key."""
    k_in, k_out = shapes(dims)[name]
    key = jax.random.fold_in(layer_key, PROJECTIONS.index(name))
    return uniform(key, (k_in, k_out), k_in)


def embedding(key, dims) -> jax.Array:
    return uniform(key, (dims.table_rows, dims.d), 1024)


def head(key, dims) -> jax.Array:
    return uniform(key, (dims.d, dims.table_rows), dims.d)


@functools.partial(jax.jit, static_argnums=(1,))
def program_params(keys, dims):
    """The program's parameter tree (``repro.models.lm`` layout, layers
    stacked on the leading axis), made on the device in one call."""
    k_embed, k_head, k_layers = keys
    n = dims.layers

    def layer(r):
        lk = jax.random.fold_in(k_layers, r)
        return {name: projection(lk, name, dims) for name in PROJECTIONS}

    w = jax.vmap(layer)(jnp.arange(n))
    bias = lambda name: jnp.zeros((n, shapes(dims)[name][1]), F32)  # noqa: E731
    params = {
        "embed": embedding(k_embed, dims),
        "final_norm": jnp.ones((dims.d,), F32),
        "blocks": {
            "slot0": {
                "norm1": jnp.ones((n, dims.d), F32),
                "norm2": jnp.ones((n, dims.d), F32),
                "attn": {
                    "q": {"w": w["q"], "b": bias("q")},
                    "k": {"w": w["k"], "b": bias("k")},
                    "v": {"w": w["v"], "b": bias("v")},
                    "o": {"w": w["o"]},
                },
                "ffn": {name: {"w": w[name]} for name in ("w1", "w3", "w2")},
            }
        },
    }
    if not dims.tied:
        params["head"] = head(k_head, dims)
    return params
