"""The benchmark's own weights, made on the device from ``--seed``.

The program serves whatever its engine's initialisation makes.  The
harness hands the engine ``fill`` as that initialisation, so the engine
makes these weights itself, in one jitted call, in the dtype it serves;
after the window ``make`` builds the same weights again from the seed for
the reference, which so takes nothing the program holds.  The tree's
layout (leaf paths, shapes, dtypes) is the program's; the values depend
only on the key and the leaf's path:

  - a leaf named ``scale`` (a norm's gain): uniform in [0.8, 1.2];
  - a leaf named ``b*`` or ``bias``: uniform with standard deviation 0.02;
  - ``embed`` (rows are tokens): standard deviation ``d_model ** -0.5``;
  - any other matrix ``(..., fan_in, fan_out)``: standard deviation
    ``fan_in ** -0.5``, the program's own fan-in scale.

Uniform draws keep each leaf at 16 random bits per value, so no float32
copy of a leaf is made on the device.
"""
from __future__ import annotations

import zlib
from typing import Any

import jax
import numpy as np


def seed32(seed: int, stream: str) -> int:
    """A 31-bit seed for one named stream of a run's ``--seed``."""
    words = np.random.SeedSequence(
        [seed, zlib.crc32(stream.encode())]).generate_state(1)
    return int(words[0]) & 0x7FFFFFFF


def key(seed: int):
    """The key the weights of a run's ``--seed`` are drawn from."""
    return jax.random.key(seed32(seed, "weights"), impl="rbg")


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def _bounds(path, shape) -> tuple:
    name = _leaf_name(path)
    if name == "scale":
        return 0.8, 1.2
    if name == "bias" or (name.startswith("b") and len(shape) <= 2):
        a = 0.02 * 3 ** 0.5
        return -a, a
    std = shape[-1] ** -0.5 if name == "embed" else shape[-2] ** -0.5
    a = std * 3 ** 0.5
    return -a, a


def spec_of(tree) -> Any:
    """Shape-and-dtype template of a tree of arrays."""
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def fill(template, key):
    """Weights shaped like ``template`` from ``key`` (traceable)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    out = []
    for i, (path, sd) in enumerate(leaves):
        lo, hi = _bounds(path, sd.shape)
        out.append(jax.random.uniform(
            jax.random.fold_in(key, i), sd.shape, sd.dtype, lo, hi))
    return jax.tree_util.tree_unflatten(treedef, out)


def make(template, seed: int):
    """The weights of ``--seed``, on the default device: the same values
    the engine made from ``fill`` with ``key(seed)``."""
    build = jax.jit(lambda k: fill(template, k))
    return jax.block_until_ready(build(key(seed)))
