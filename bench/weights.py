"""Seeded weights for a benchmark configuration, made by the benchmark.

Every leaf is a function of ``(seed, leaf name, layer)`` alone, so the
served model can be made in one jitted call on the device and the
reference can make any one layer again, in float32, without touching
what the program holds.  Values are drawn in float32 and rounded once
to the served dtype; the reference upcasts those same rounded values.

The leaves, their shapes and their layer groups come from the
configuration's architecture module (``bench/archs/``); their dotted
names follow the nesting of the served parameter tree, and
``bench/program.py`` checks the tree built from them against the
program's own before serving.  A layer leaf's layer is its index inside
its group.
"""
from __future__ import annotations

import zlib

import numpy as np


def root_key(seed: int):
    """A threefry key from a seed of any size (the driver's seeds pass 32
    bits)."""
    import jax.numpy as jnp
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def leaf(key, name: str, shape: tuple, mean: float, std: float, layer,
         dtype):
    """One leaf (one layer of it when ``layer`` is not None), drawn in
    float32 and rounded to ``dtype``."""
    import jax
    import jax.numpy as jnp
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    x = mean + std * jax.random.normal(k, shape, jnp.float32)
    return x.astype(dtype)


def _put(node: dict, path: list[str], x) -> None:
    *parents, last = path
    for p in parents:
        node = node.setdefault(p, {})
    node[last] = x


def make_tree(arch, s: dict, key, dtype):
    """The whole nested weight tree of module ``arch`` with sizes ``s``:
    a stacked group's leaves stacked on axis 0, a list group as a list of
    per-layer trees.  One traceable function of the key from
    :func:`root_key`: call it under ``jax.jit`` with the key as an
    argument, so one compiled program serves every seed."""
    import jax
    import jax.numpy as jnp
    layout = {g: (n, how) for g, n, how in arch.groups(s)}
    tree: dict = {}
    for name, group, shape, mean, std in arch.leaves(s):
        path = name.split(".")
        if group is None:
            _put(tree, path, leaf(key, name, shape, mean, std, None, dtype))
            continue
        n, how = layout[group]
        if how == "stacked":
            x = jax.vmap(lambda i, n=name, sh=shape, m=mean, sd=std: leaf(
                key, n, sh, m, sd, i, dtype))(jnp.arange(n))
            _put(tree, path, x)
        elif how == "list":
            layers = tree.setdefault(group, [{} for _ in range(n)])
            for i in range(n):
                _put(layers[i], path[1:],
                     leaf(key, name, shape, mean, std, i, dtype))
        else:
            raise ValueError(f"group {group!r}: unknown layout {how!r}")
    return tree


def layer_weights(arch, s: dict, key, group: str, layer, dtype):
    """One layer of ``group``, by leaf path inside the group, in ``dtype``
    (the reference calls this with the served dtype and upcasts)."""
    return {name.split(".", 1)[1]: leaf(key, name, shape, mean, std, layer,
                                        dtype)
            for name, g, shape, mean, std in arch.leaves(s) if g == group}


def global_weights(arch, s: dict, key, dtype):
    """The leaves outside the layers (the embedding, the head when
    untied, the final norm), by name."""
    return {name: leaf(key, name, shape, mean, std, None, dtype)
            for name, g, shape, mean, std in arch.leaves(s) if g is None}
