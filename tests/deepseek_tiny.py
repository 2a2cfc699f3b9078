"""A test-sized DeepSeek-V3 decoder for the latent-attention and
held-expert tests: latent attention (no query LoRA), one leading dense
layer, then expert layers of sigmoid-routed experts with a correction bias
and a routed scaling, two shared experts, and 4 of the 8 experts held
here (experts 2-5)."""
import zlib

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
from repro.models.registry import fns_for


def config(held=(2, 4), dtype="float32") -> ModelConfig:
    moe = MoEConfig(num_experts=8, top_k=3, d_ff_expert=32,
                    num_shared_experts=2, d_ff_shared=32, first_k_dense=1,
                    d_ff_dense=96, scoring="sigmoid", router_bias=True,
                    routed_scaling=2.446, held=held)
    return ModelConfig(
        name="deepseek-v3-tiny", family="moe", num_layers=3, d_model=64,
        num_heads=4, num_kv_heads=4, d_ff=32, vocab_size=128,
        rope_theta=50_000.0, norm_eps=1e-5, moe=moe,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        param_dtype=dtype, compute_dtype=dtype)


def params(cfg, seed=0):
    """Seeded weights with every norm scale and the correction bias drawn
    away from their initial ones and zeros, so neither is a no-op."""
    p = fns_for(cfg).init(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1000)

    def draw(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        if "router_bias" in name:
            return (0.05 * jax.random.normal(k, x.shape)).astype(x.dtype)
        if "scale" in name or "kv_norm" in name:
            return (1 + 0.1 * jax.random.normal(k, x.shape)).astype(x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(draw, p)


def tokens(n, seed=0, vocab=128):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, n), 0, vocab,
                              jnp.int32)
