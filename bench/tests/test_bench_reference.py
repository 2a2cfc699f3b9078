"""bench/reference.py, running the equations of bench/archs/qwen_dense.py,
against the program at a test size: its logits equal the program's dense
forward run in float32 on the same weights, for both configurations'
switches (QKV bias with a tied head; qk_norm with an untied head)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.archs import qwen_dense as QD
from bench import reference as R
from bench import weights as W
from bench.tests import tiny


@pytest.mark.parametrize("bias,qk_norm,tie", [(True, False, True),
                                              (False, True, False)],
                         ids=["qwen2-switches", "qwen3-switches"])
def test_reference_matches_the_program_in_float32(bias, qk_norm, tie):
    from repro.models.registry import fns_for
    conf = tiny.conf()
    conf["architecture"] = {"qkv_bias": bias, "qk_norm": qk_norm}
    conf["tie_word_embeddings"] = tie
    seed = 2 ** 33 + 5
    s = QD.spec_of(conf)
    cfg = QD.model_config(conf).replace(compute_dtype="float32",
                                        param_dtype="float32")
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        W.make_tree(QD, s, W.root_key(seed), jnp.bfloat16))
    toks = np.random.default_rng(0).integers(0, s["V"], 97).astype(np.int32)
    lg, _ = fns_for(cfg).forward(cfg, params, {"tokens": jnp.asarray(toks)[None]})
    lg = np.asarray(lg[0, :-1], np.float64)       # (S - 1, V): reads 0..S-2
    smp = R.Sample([toks[:1]], [toks[1:]], len(toks), len(toks) - 1)
    with jax.default_matmul_precision("highest"):
        h = R.final_hidden(conf, seed, smp)
        mx, at, top = R.head_stats(conf, seed, h, smp.served)
    m = smp.mask.reshape(-1)
    mx, at, top = mx[m], at[m], top[m]
    want_at = lg[np.arange(len(toks) - 1), toks[1:]]
    scale = np.abs(lg).max()
    assert np.abs(mx - lg.max(-1)).max() < 1e-4 * scale
    assert np.abs(at - want_at).max() < 1e-4 * scale
    assert (top == lg.argmax(-1)).mean() > 0.98


def test_served_gaps_are_zero_for_the_reference_argmax():
    conf = tiny.conf()
    seed = 17
    toks = np.random.default_rng(1).integers(0, 256, 40).astype(np.int32)
    smp = R.Sample([toks], [[0, 0]], 64, 8)
    h = R.final_hidden(conf, seed, smp)
    _, _, top = R.head_stats(conf, seed, h, smp.served)
    first = int(top.reshape(smp.served.shape)[0, 0])
    smp = R.Sample([toks], [[first]], 64, 8)
    assert R.served_gaps(conf, seed, smp).tolist() == [0.0]
