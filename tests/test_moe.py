"""MoE: routing invariants + dispatch-strategy equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, strategies as st

from repro.configs.base import MoEConfig
from repro.models.layers import moe as M


def _setup(E=8, k=2, d=16, f=32, cf=8.0, seed=0):
    cfg = MoEConfig(num_experts=E, top_k=k, d_ff_expert=f,
                    capacity_factor=cf)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    params = {
        "router": jax.random.normal(ks[0], (d, E)) * 0.1,
        "w_gate": jax.random.normal(ks[1], (E, d, f)) * 0.1,
        "w_up": jax.random.normal(ks[2], (E, d, f)) * 0.1,
        "w_down": jax.random.normal(ks[3], (E, f, d)) * 0.1,
    }
    x = jax.random.normal(ks[4], (2, 12, d))
    return cfg, params, x


def test_route_shapes_and_norm():
    cfg, params, x = _setup()
    idx, prob, aux = M.route(cfg, params, x)
    assert idx.shape == (2, 12, 2) and prob.shape == (2, 12, 2)
    np.testing.assert_allclose(prob.sum(-1), 1.0, atol=1e-5)  # norm_topk
    # top-k experts are distinct per token
    assert bool(jnp.all(idx[..., 0] != idx[..., 1]))
    assert float(aux) > 0


def test_einsum_matches_dense():
    cfg, params, x = _setup()
    idx, prob, _ = M.route(cfg, params, x)
    y_d = M.moe_dense(cfg, params, x, idx, prob)
    y_e = M.moe_einsum(cfg, params, x, idx, prob)
    np.testing.assert_allclose(y_d, y_e, atol=1e-5)


def test_capacity_drops_reduce_output():
    """With capacity 1 some tokens are dropped -> output differs from
    dropless, and dropped tokens contribute zero."""
    cfg, params, x = _setup(cf=8.0)
    idx, prob, _ = M.route(cfg, params, x)
    y_full = M.moe_einsum(cfg, params, x, idx, prob)
    y_tight = M.moe_einsum(cfg, params, x, idx, prob, capacity=1)
    assert float(jnp.abs(y_full - y_tight).max()) > 1e-6


def test_aux_loss_balanced_vs_skewed():
    """Uniform routing minimizes the Switch aux loss."""
    cfg, params, x = _setup(E=4, k=1, seed=3)
    # craft logits: perfectly uniform vs all-to-one
    B, S, E = 2, 12, 4
    uniform = jnp.zeros((B, S, E))
    skewed = jnp.where(jnp.arange(E) == 0, 10.0, -10.0)[None, None, :]
    skewed = jnp.broadcast_to(skewed, (B, S, E))

    def aux_of(logits):
        probs = jax.nn.softmax(logits, -1)
        prob, idx = jax.lax.top_k(probs, 1)
        one_hot = jax.nn.one_hot(idx, E)
        frac = jnp.mean(jnp.sum(one_hot, 2), (0, 1))
        mean_p = jnp.mean(probs, (0, 1))
        return float(E * jnp.sum(frac * mean_p))

    assert aux_of(skewed) > aux_of(uniform) * 2


@given(seed=st.integers(0, 20))
def test_moe_grad_flows(seed):
    cfg, params, x = _setup(seed=seed)

    def loss(p, x):
        idx, prob, aux = M.route(cfg, p, x)
        y = M.moe_einsum(cfg, p, x, idx, prob)
        return jnp.sum(y ** 2) + aux

    g = jax.grad(loss)(params, x)
    gn = sum(float(jnp.abs(l).sum()) for l in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gn) and gn > 0


# -- sigmoid routing and the held experts' dropless share ---------------------

def _held_setup(held=(2, 4), T=24, d=64, seed=0):
    """Routing inputs and one expert layer of the tiny DeepSeek-V3 at
    float32, its stacks cut to ``held``."""
    import deepseek_tiny as DT
    cfg = DT.config(held=(0, 8))
    full = jax.tree_util.tree_map(lambda a: a[0], DT.params(cfg)["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(seed + 7), (1, T, d))
    return cfg.moe, full, x


def _cut(cfg_moe, full, held):
    """The layer as the chip holding experts ``held`` = (first, count)
    holds it: the router whole, the stacks sliced."""
    first, n = held
    moe = {k: (v[first:first + n] if k in M.EXPERT_STACKS else v)
           for k, v in full["moe"].items()}
    return dataclasses.replace(cfg_moe, held=held), moe


def test_sigmoid_bias_scaled_routing_matches_numpy():
    """Sigmoid scores; the top k of score + correction bias; weights the
    chosen scores normalised over all k choices, times the scaling."""
    cfg_moe, full, x = _held_setup()
    p = full["moe"]
    idx, prob, _ = M.route(cfg_moe, p, x)
    h = np.asarray(x, np.float64)[0]
    s = 1 / (1 + np.exp(-(h @ np.asarray(p["router"], np.float64))))
    choose = s + np.asarray(p["router_bias"], np.float64)
    want = np.argsort(-choose, axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.asarray(idx[0]), want)
    w = np.take_along_axis(s, want, -1)
    w = 2.446 * w / w.sum(-1, keepdims=True)
    # float32 routing against a float64 hand computation
    np.testing.assert_allclose(np.asarray(prob[0]), w, rtol=1e-5)
    # the bias changed some choice: without it the top 3 differ somewhere
    plain = np.argsort(-s, axis=-1, kind="stable")[:, :3]
    assert (np.sort(plain, -1) != np.sort(want, -1)).any()


def test_held_shares_add_up_to_the_uncut_layer():
    """Four chips holding experts 0-1, 2-3, 4-5 and 6-7: their routed
    shares, with the shared experts counted once, add up to the layer
    that holds all eight."""
    from repro.models import transformer as TR
    import deepseek_tiny as DT
    cfg_moe, full, x = _held_setup()
    cfg = DT.config(held=(0, 8))
    uncut, _, _ = TR._ffn_apply(cfg, full, x)
    shared = TR.swiglu(full["shared"], x)
    total = -3 * shared
    for first in (0, 2, 4, 6):
        c, moe = _cut(cfg_moe, full, (first, 2))
        y, _, _ = TR._ffn_apply(cfg.replace(moe=c), dict(full, moe=moe), x)
        total = total + y
    # float32 sums of the same terms in another order
    np.testing.assert_allclose(total, uncut,
                               atol=1e-5 * float(jnp.abs(uncut).max()))
    # and the uncut dropless layer is the every-expert-on-every-token oracle
    idx, prob, _ = M.route(cfg_moe, full["moe"], x)
    dense = M.moe_dense(cfg_moe, full["moe"], x, idx, prob) + shared
    np.testing.assert_allclose(uncut, dense,
                               atol=1e-5 * float(jnp.abs(dense).max()))


def test_held_counts_rows_and_experts():
    cfg_moe, full, x = _held_setup()
    c, moe = _cut(cfg_moe, full, (2, 4))
    idx, prob, _ = M.route(c, moe, x)
    _, counts = M.moe_held(c, moe, x, idx, prob)
    local = np.asarray(idx).reshape(-1) - 2
    mine = local[(local >= 0) & (local < 4)]
    assert np.asarray(counts).tolist() == [len(mine), len(set(mine))]


@pytest.mark.parametrize("pallas", [False, True], ids=["ref", "interpret"])
def test_held_token_output_is_independent_of_its_batch_and_chunk(pallas):
    """No capacity: a token's expert output is the same bits alone, inside
    a batch of other tokens, and inside chunks of other widths."""
    from repro.kernels.dispatch import pallas_enabled
    cfg_moe, full, x = _held_setup(T=40)
    c, moe = _cut(cfg_moe, full, (2, 4))
    x = x.astype(jnp.bfloat16)
    moe = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), moe)

    def share(xs):
        idx, prob, _ = M.route(c, moe, xs)
        return M.moe_held(c, moe, xs, idx, prob)[0]

    with pallas_enabled(pallas):
        whole = share(x)
        for width in (1, 8, 16):
            parts = [share(x[:, i:i + width])
                     for i in range(0, x.shape[1], width)]
            np.testing.assert_array_equal(jnp.concatenate(parts, 1), whole,
                                          err_msg=f"chunks of {width}")
        others = jax.random.normal(jax.random.PRNGKey(99), (1, 200, 64),
                                   jnp.bfloat16)
        mixed = share(jnp.concatenate([others[:, :100], x, others[:, 100:]],
                                      1))
        np.testing.assert_array_equal(mixed[:, 100:140], whole)
