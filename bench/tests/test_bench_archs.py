"""A configuration names its architecture module, and a second
architecture enters the harness by files of its own: the test-only module
``archs/moe_smoke.py`` (the program's ``moe`` family at smoke size) with
its configuration ``data/tiny_moe.json``, found by pointing the module
lookup at ``bench.tests.archs``."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import archs
from bench import program as P
from bench import reference as R
from bench import run as RUN
from bench import trace_reduce as TR
from bench import weights as W
from bench.tests import tiny


@pytest.fixture
def moe(monkeypatch):
    monkeypatch.setattr(archs, "PACKAGE", "bench.tests.archs")
    conf = tiny.conf("tiny_moe")
    return conf, archs.of(conf)


def test_a_configuration_names_its_module():
    conf = tiny.conf()
    assert archs.of(conf).__name__ == "bench.archs.qwen_dense"
    del conf["bench_arch"]
    with pytest.raises(KeyError):
        archs.of(conf)
    with pytest.raises(ValueError):
        archs.of(dict(conf, bench_arch="../qwen_dense"))


def test_moe_tree_passes_the_programs_check(moe):
    conf, arch = moe
    params = P.init_params(conf, arch.model_config(conf), 2 ** 33 + 9,
                           jax.devices()[0])
    assert len(params["dense_blocks"]) == 1
    assert params["dense_blocks"][0]["mlp"]["w_gate"].shape == (64, 128)
    assert params["blocks"]["moe"]["w_gate"].shape == (2, 8, 64, 32)
    assert params["blocks"]["shared"]["w_gate"].shape == (2, 64, 64)


def test_moe_reference_matches_the_program_in_float32(moe):
    from repro.models.registry import fns_for
    conf, arch = moe
    seed = 2 ** 33 + 5
    s = arch.spec_of(conf)
    cfg = arch.model_config(conf).replace(compute_dtype="float32",
                                          param_dtype="float32")
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        W.make_tree(arch, s, W.root_key(seed), jnp.bfloat16))
    toks = np.random.default_rng(0).integers(0, s["V"], 97).astype(np.int32)
    lg, _ = fns_for(cfg).forward(cfg, params, {"tokens": jnp.asarray(toks)[None]})
    lg = np.asarray(lg[0, :-1], np.float64)
    smp = R.Sample([toks[:1]], [toks[1:]], len(toks), len(toks) - 1)
    with jax.default_matmul_precision("highest"):
        h = R.final_hidden(conf, seed, smp)
        mx, at, top = R.head_stats(conf, seed, h, smp.served)
    m = smp.mask.reshape(-1)
    mx, at, top = mx[m], at[m], top[m]
    scale = np.abs(lg).max()
    assert np.abs(mx - lg.max(-1)).max() < 1e-4 * scale
    assert np.abs(at - lg[np.arange(len(toks) - 1), toks[1:]]).max() < 1e-4 * scale
    assert (top == lg.argmax(-1)).mean() > 0.98


def test_moe_closed_loop_run_is_correct(monkeypatch, moe):
    res, printed = tiny.run(monkeypatch, "tiny.reasoning", 2 ** 31 + 77,
                            config="tiny_moe")
    assert res["correct"] is True, printed
    assert list(res["checks"]) == ["gap_p95"]
    gap = res["checks"]["gap_p95"]
    assert gap["limit"] == moe[0]["check"]["gap_p95_limit"]
    assert 0 <= gap["value"] <= gap["limit"]
    assert res["metrics"]["output_tok_s"]["value"] > 0


def test_moe_control_is_not_correct(monkeypatch, moe):
    """The fp8 control, judged in the program's place by the harness's own
    comparison of the fixture's number, comes out not correct; the sound
    program's reading on the same sample stays within the limit."""
    res, printed = tiny.run(monkeypatch, "tiny.reasoning", 2 ** 32 + 1301,
                            control=True, config="tiny_moe")
    gap = res["checks"]["gap_p95"]
    program = float(re.search(r"served tokens, .*gap_p95 ([0-9.e-]+);",
                              printed).group(1))
    control = float(re.search(r"control fp8: .*gap_p95 ([0-9.e-]+);",
                              printed).group(1))
    assert 0 <= program <= gap["limit"], printed
    assert gap["value"] == control > gap["limit"], printed
    assert res["correct"] is False, printed
    assert res["failed"] == 0 and res["attempted"] > 3


def test_moe_kernel_lands_in_its_kernel_s(moe):
    conf, arch = moe
    dev = "/device:TPU:0"
    events = [TR.Event(dev, "XLA Modules", "jit_decode_step", 0.0, 1.0),
              TR.Event(dev, "XLA Ops", "paged_decode_attention.2", 0.0, 0.25),
              TR.Event(dev, "XLA Ops", "moe_expert_ffn.7", 0.3, 0.5),
              TR.Event(dev, "XLA Modules", "jit_other", 1.5, 0.5),
              TR.Event(dev, "XLA Ops", "moe_expert_ffn.9", 1.5, 0.5)]
    red = TR.reduce(events, (0.0, 2.0), 1, arch.KERNELS)
    assert red.kernel_s == {"decode_attn": 0.25, "expert_ffn": 1.0}
    assert red.program_s == {"decode": 1.0, "other": 0.5}
    # its work, for 3 decoded tokens and one 20-row prompt: 6 d fe FLOPs
    # per token, routed expert and expert layer
    snaps = [{"t": 0.0, "prefill_rows": 0, "reqs": {1: (10, 0, 1, -1.0)}},
             {"t": 1.0, "prefill_rows": 20,
              "reqs": {1: (10, 0, 4, -1.0), 2: (20, 0, 1, 0.5)}}]
    work = RUN.span_work(arch, arch.spec_of(conf), snaps, 16, 32)
    assert work["expert_ffn"].flops == 6 * 64 * 32 * 2 * 2 * (3 + 20)
    assert work["decode_tokens"] == 3 and work["prefill_rows"] == 20
