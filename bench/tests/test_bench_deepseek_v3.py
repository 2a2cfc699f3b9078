"""The ``deepseek_v3`` architecture module on a tiny data file
(``data/tiny_deepseek_v3.json``: latent attention, one dense layer, two
layers of sigmoid-routed experts of which 4 of 8 are held here): its tree
is the program's, its float32 reference in the published form matches the
program's forward, a closed loop through the harness is correct and its
fp8 control is not, the program counts its routed work, and the reader
``kernel.expert_ffn_roofline`` reads the grouped kernel off a trace."""
import io
import json
import re
from pathlib import Path

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
from bench.peaks import peaks_for
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture
def ds():
    conf = tiny.conf("tiny_deepseek_v3")
    return conf, archs.of(conf)


def test_tree_matches_the_programs(ds):
    conf, arch = ds
    params = P.init_params(conf, arch.model_config(conf), 2 ** 33 + 11,
                           jax.devices()[0])
    attn = params["blocks"]["attn"]
    assert attn["wkv_a"].shape == (2, 64, 32 + 16)
    assert attn["wkv_b"].shape == (2, 32, 4, 16 + 16)
    assert params["blocks"]["moe"]["router"].shape == (2, 64, 8)
    assert params["blocks"]["moe"]["router_bias"].shape == (2, 8)
    assert params["blocks"]["moe"]["w_gate"].shape == (2, 4, 64, 32)
    assert params["dense_blocks"][0]["mlp"]["w_gate"].shape == (64, 128)


def test_reference_matches_the_program_in_float32(ds):
    """The reference (published form, held experts' share) and the
    program's own forward (its published form too, dropless held experts)
    on the same seeded weights, both in float32."""
    from repro.models.registry import fns_for
    conf, arch = ds
    seed = 2 ** 33 + 5
    s = arch.spec_of(conf)
    cfg = arch.model_config(conf).replace(compute_dtype="float32",
                                          param_dtype="float32")
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        W.make_tree(arch, s, W.root_key(seed), jnp.bfloat16))
    toks = np.random.default_rng(0).integers(0, s["V"], 97).astype(np.int32)
    lg, _ = fns_for(cfg).forward(cfg, params,
                                 {"tokens": jnp.asarray(toks)[None]},
                                 remat=False)
    lg = np.asarray(lg[0, :-1], np.float64)
    smp = R.Sample([toks[:1]], [toks[1:]], len(toks), len(toks) - 1)
    with jax.default_matmul_precision("highest"):
        h = R.final_hidden(conf, seed, smp)
        mx, at, top = R.head_stats(conf, seed, h, smp.served)
    m = smp.mask.reshape(-1)
    mx, at, top = mx[m], at[m], top[m]
    # float32 on both sides, the same terms in another order
    scale = np.abs(lg).max()
    assert np.abs(mx - lg.max(-1)).max() < 1e-4 * scale
    assert np.abs(at - lg[np.arange(len(toks) - 1), toks[1:]]).max() \
        < 1e-4 * scale
    assert (top == lg.argmax(-1)).mean() > 0.98


def test_closed_loop_run_is_correct(monkeypatch, ds):
    res, printed = tiny.run(monkeypatch, "tiny.reasoning", 2 ** 31 + 91,
                            config="tiny_deepseek_v3")
    assert res["correct"] is True, printed
    assert list(res["checks"]) == ["gap_p95"]
    assert 0 <= res["checks"]["gap_p95"]["value"] <= 0.05
    assert res["metrics"]["output_tok_s"]["value"] > 0


def test_control_is_not_correct(monkeypatch, ds):
    res, printed = tiny.run(monkeypatch, "tiny.reasoning", 2 ** 32 + 1607,
                            control=True, config="tiny_deepseek_v3")
    program = float(re.search(r"served tokens, .*gap_p95 ([0-9.e-]+);",
                              printed).group(1))
    gap = res["checks"]["gap_p95"]
    assert 0 <= program <= gap["limit"], printed
    assert gap["value"] > gap["limit"] and res["correct"] is False, printed


def test_the_program_counts_its_routed_work(ds):
    """Served through the benchmark's own `build_fleet`, the window's
    stats carry the held experts' rows and touched experts."""
    from repro.serving.engine import Request
    conf, arch = ds
    cfg = arch.model_config(conf)
    fleet = P.build_fleet(conf, cfg, P.init_params(conf, cfg, 17,
                                                   jax.devices()[0]))
    eng = fleet.engines[0]
    rng = np.random.default_rng(1)
    stats = eng.serve([Request(i, rng.integers(0, 256, 40, dtype=np.int32),
                               max_new_tokens=5) for i in range(3)])
    assert 0 < stats.moe_expert_hits <= stats.moe_rows


def test_expert_ffn_reader_on_a_synthetic_trace(ds):
    """A trace whose kernel ``moe_expert_ffn`` ran 2 ms in a span that
    decoded 30 of the window's 300 tokens and prefilled 40 of its 200
    rows: the decode programs' counts taken a tenth, the prefill
    programs' a fifth, over 2 ms at the v5e's peaks."""
    from repro.serving.engine import ServeStats
    conf, arch = ds
    s = arch.spec_of(conf)
    dev = "/device:TPU:0"
    events = [TR.Event(dev, "XLA Modules", "jit_decode_step", 0.0, 0.01),
              TR.Event(dev, "XLA Ops", "paged_decode_attention.1", 0.0,
                       0.001),
              TR.Event(dev, "XLA Ops", "moe_expert_ffn.3", 0.002, 0.002)]
    red = TR.reduce(events, (0.0, 0.01), 1, arch.KERNELS)
    assert red.kernel_s == {"decode_attn": 0.001, "expert_ffn": 0.002}
    snaps = [{"t": 0.0, "prefill_rows": 0, "reqs": {1: (10, 0, 1, -1.0)}},
             {"t": 1.0, "prefill_rows": 40, "reqs": {1: (10, 0, 31, -1.0)}}]
    work = RUN.span_work(arch, s, snaps, 16, 32)
    assert (work["decode_tokens"], work["prefill_rows"]) == (30, 40)
    stats = ServeStats(tokens=300, prefill_tokens_computed=200,
                       moe_rows=5000, moe_expert_hits=800,
                       moe_rows_prefill=1000, moe_expert_hits_prefill=300)
    out = io.StringIO()
    ctx = dict(stats=stats, trace=red, work=work,
               peaks=peaks_for("TPU v5 lite"), spec=s, out=out)
    v = RUN.load_reader("kernel.expert_ffn_roofline")(ctx)
    d, fe = 64, 32
    rows, hits = 4000 / 10 + 1000 / 5, 500 / 10 + 300 / 5
    byt = hits * 3 * d * fe * 2 + rows * 2 * d * 2
    assert v == pytest.approx(100 * byt / 819e9 / 0.002)
    assert "memory-bound" in out.getvalue()
    # a program without the counters reports nothing
    stats.moe_rows = 0
    assert RUN.load_reader("kernel.expert_ffn_roofline")(ctx) is None


def test_the_benchmark_configuration_keeps_the_published_widths():
    """Every number of the published config.json is in the file as
    published but the held experts' count, listed in ``reduced``; the
    file names the published count and the chip's share beside it."""
    conf = json.loads((ROOT / "bench/configs/moonlight-16b-a3b.json")
                      .read_text())
    arch = archs.of(conf)
    s = arch.spec_of(conf)
    assert conf["reduced"] == ["n_routed_experts"]
    assert (s["held"], s["E"], s["first"]) == (16, 64, 0)
    assert (s["L"], s["d"], s["H"], s["r"], s["dn"], s["dr"], s["dv"]) == (
        27, 2048, 16, 512, 128, 64, 128)
    assert (s["f"], s["fe"], s["k"], s["shared"], s["V"]) == (
        11264, 1408, 6, 2, 163840)
    n = sum(int(np.prod(shape)) * (1 if g is None else
                                   {"dense_blocks": 1, "blocks": 26}[g])
            for _, g, shape, _, _ in arch.leaves(s))
    assert n == 5_163_971_712          # 10.33 GB of bf16 weights
