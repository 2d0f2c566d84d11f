"""Seeded random weights of a block stack, made by the benchmark.

The benchmark, not the program, makes the weights: the served model gets
them in the dtype it serves (bfloat16), built on the device in one jitted
call, and the plain reference makes the same values again, layer by layer,
in float32. Both go through `leaf`, so a value is the bfloat16 rounding of
one uniform draw, whichever side asks for it.

Which leaves there are is the family's to say (`bench/reference/<family>.py`,
`leaves`): their names, shapes, inits and scales. Names follow the program's
parameter tree (``attn.wq`` is ``layers.attn.wq``), and shapes the layer
equations (``attn.wq`` is ``[d, heads, head_dim]``); `bench/drivers/serve.py`
maps them onto that tree and checks that the shapes agree.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

SERVED_DTYPE = jnp.bfloat16

Shape = Tuple[int, ...]


class Leaves(NamedTuple):
    """The leaves a family draws. A per-layer leaf is stacked over `layers`
    and drawn with its place in the sorted names as its index; a global leaf
    has an index of its own, past the per-layer ones. `init` is ``gain``
    (norm weights near 1) or ``fan_in`` (uniform with standard deviation
    `scale`)."""
    layers: int
    per_layer: Dict[str, Tuple[Shape, str, float]]       # name -> (shape, init, scale)
    glob: Dict[str, Tuple[int, Shape, str, float]]       # name -> (index, shape, init, scale)


def base_key(seed: int):
    """A key from a seed of any size (the driver's seeds pass 2**32)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf(key, index: int, layer: int, shape, init: str, scale: float):
    """One leaf (or one layer of a stacked leaf), rounded to bfloat16."""
    k = jax.random.fold_in(jax.random.fold_in(key, index), layer)
    if init == "gain":
        u = jax.random.uniform(k, shape, jnp.float32, -0.2, 0.2)
        return (1.0 + u).astype(SERVED_DTYPE)
    a = scale * math.sqrt(3.0)
    return jax.random.uniform(k, shape, jnp.float32, -a, a).astype(SERVED_DTYPE)


def make_params(spec: Leaves, seed: int) -> Dict[str, jax.Array]:
    """Every leaf, stacked over layers, in bfloat16, on the default device.
    One jitted call; each layer is drawn inside a `lax.map`, so the float32
    draw of one layer is the largest temporary."""

    def build(key):
        out = {}
        for i, (name, (shape, init, scale)) in enumerate(sorted(spec.per_layer.items())):
            out[name] = jax.lax.map(
                lambda l, i=i, shape=shape, init=init, scale=scale:
                    leaf(key, i, l, shape, init, scale),
                jnp.arange(spec.layers))
        for name, (i, shape, init, scale) in spec.glob.items():
            out[name] = leaf(key, i, 0, shape, init, scale)
        return out

    return jax.jit(build)(base_key(seed))


def layer_f32(spec: Leaves, seed: int, layer: int) -> Dict[str, jax.Array]:
    """One layer's leaves as float32 (the bfloat16 values, widened)."""
    key = base_key(seed)
    return {name: _leaf_jit(key, i, layer, shape, init, scale).astype(jnp.float32)
            for i, (name, (shape, init, scale)) in enumerate(sorted(spec.per_layer.items()))}


def global_f32(spec: Leaves, seed: int) -> Dict[str, jax.Array]:
    key = base_key(seed)
    return {name: _leaf_jit(key, i, 0, shape, init, scale).astype(jnp.float32)
            for name, (i, shape, init, scale) in spec.glob.items()}


_leaf_jit = jax.jit(leaf, static_argnums=(1, 3, 4, 5))
