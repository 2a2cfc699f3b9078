"""Seeded weights for a benchmark configuration, made by the benchmark.

Every leaf is a function of ``(seed, leaf name, layer)`` alone, so the
served model can be made in one jitted call on the device and the
reference can make any one layer again, in float32, without touching
what the program holds.  Values are drawn in float32 and rounded once
to the served dtype; the reference upcasts those same rounded values.

The leaf names follow the nesting of the served parameter tree
(``embed.tok``, ``blocks.attn.wq`` ...); ``bench/program.py`` checks the
tree it builds from them against the program's own before serving.
"""
from __future__ import annotations

import zlib

import numpy as np


def spec_of(conf: dict) -> dict:
    """The sizes and switches of a configuration file, by short name."""
    arch = conf["architecture"]
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return dict(
        L=conf["num_hidden_layers"], d=d, H=H,
        K=conf["num_key_value_heads"],
        hd=conf.get("head_dim") or d // H,
        f=conf["intermediate_size"], V=conf["vocab_size"],
        tie=bool(conf["tie_word_embeddings"]),
        eps=float(conf["rms_norm_eps"]), theta=float(conf["rope_theta"]),
        qkv_bias=bool(arch["qkv_bias"]), qk_norm=bool(arch["qk_norm"]),
        dtype=conf["torch_dtype"])


def leaves(s: dict) -> list[tuple[str, bool, tuple, float, float]]:
    """(name, per_layer, shape, mean, stddev) of every weight.  Matrices
    draw with stddev 1/sqrt(fan-in over the contracted dims); norm scales
    draw around 1 and biases around 0, so neither is a no-op.  The
    embedding draws with stddev 1/sqrt(hidden size), as a matrix read
    as the head: a tied head's logits then have unit scale, and the
    current token's own row adds only about 1/sqrt(2 L) to its own
    logit, so the served token depends on the layers and the cache, not
    on the token before it alone."""
    d, H, K, hd, f, V = s["d"], s["H"], s["K"], s["hd"], s["f"], s["V"]
    out = [("embed.tok", False, (V, d), 0.0, d ** -0.5)]
    if not s["tie"]:
        out.append(("embed.lm_head", False, (d, V), 0.0, d ** -0.5))
    out += [
        ("blocks.ln1.scale", True, (d,), 1.0, 0.1),
        ("blocks.attn.wq", True, (d, H, hd), 0.0, d ** -0.5),
        ("blocks.attn.wk", True, (d, K, hd), 0.0, d ** -0.5),
        ("blocks.attn.wv", True, (d, K, hd), 0.0, d ** -0.5),
        ("blocks.attn.wo", True, (H, hd, d), 0.0, (H * hd) ** -0.5),
    ]
    if s["qkv_bias"]:
        out += [("blocks.attn.bq", True, (H, hd), 0.0, 0.1),
                ("blocks.attn.bk", True, (K, hd), 0.0, 0.1),
                ("blocks.attn.bv", True, (K, hd), 0.0, 0.1)]
    if s["qk_norm"]:
        out += [("blocks.attn.q_norm", True, (hd,), 1.0, 0.1),
                ("blocks.attn.k_norm", True, (hd,), 1.0, 0.1)]
    out += [
        ("blocks.mlp.w_gate", True, (d, f), 0.0, d ** -0.5),
        ("blocks.mlp.w_up", True, (d, f), 0.0, d ** -0.5),
        ("blocks.mlp.w_down", True, (f, d), 0.0, f ** -0.5),
        ("blocks.ln2.scale", True, (d,), 1.0, 0.1),
        ("ln_f.scale", False, (d,), 1.0, 0.1),
    ]
    return out


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


def make_tree(s: dict, key, dtype):
    """The whole nested weight tree (layer leaves stacked on axis 0), as
    one traceable function of the key from :func:`root_key`: call it
    under ``jax.jit`` with the key as an argument, so one compiled
    program serves every seed."""
    import jax
    import jax.numpy as jnp
    tree: dict = {}
    for name, per_layer, shape, mean, std in leaves(s):
        if per_layer:
            x = jax.vmap(lambda i, n=name, sh=shape, m=mean, sd=std: leaf(
                key, n, sh, m, sd, i, dtype))(jnp.arange(s["L"]))
        else:
            x = leaf(key, name, shape, mean, std, None, dtype)
        node = tree
        *parents, last = name.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = x
    return tree


def layer_weights(s: dict, key, layer, dtype):
    """One layer's weights by leaf name, in ``dtype`` (the reference calls
    this with the served dtype and upcasts)."""
    return {name.split(".", 1)[1]: leaf(key, name, shape, mean, std, layer,
                                        dtype)
            for name, per_layer, shape, mean, std in leaves(s) if per_layer}


def global_weights(s: dict, key, dtype):
    """The embedding, the head when untied, and the final norm."""
    return {name: leaf(key, name, shape, mean, std, None, dtype)
            for name, per_layer, shape, mean, std in leaves(s)
            if not per_layer}
