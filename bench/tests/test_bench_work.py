"""The dense configurations' work counts (bench/archs/qwen_dense.py) and
the shared pieces of bench/work.py, against cases worked by hand for both
configurations."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import work as WK
from bench.archs import qwen_dense as QD

ROOT = Path(__file__).resolve().parents[2]


def spec(name):
    return QD.spec_of(json.loads(
        (ROOT / "bench" / "configs" / f"{name}.json").read_text()))


def test_layer_params_by_hand():
    # qwen2.5-3b: 2048*16*128 + 2*2048*2*128 + 16*128*2048 + 3*2048*11008
    assert QD.layer_matmul_params(spec("qwen2.5-3b")) == (
        4194304 + 1048576 + 4194304 + 67633152)
    # qwen3-32b: 5120*64*128 + 2*5120*8*128 + 64*128*5120 + 3*5120*25600
    assert QD.layer_matmul_params(spec("qwen3-32b-8l")) == (
        41943040 + 10485760 + 41943040 + 393216000)


def test_weights_add_up_to_the_published_sizes():
    """8 x 487.6 M + 2 x 777.9 M for qwen3-32b-8l; 3.09 B for qwen2.5-3b."""
    for name, lo, hi in (("qwen3-32b-8l", 5.45e9, 5.47e9),
                         ("qwen2.5-3b", 3.08e9, 3.10e9)):
        s = spec(name)
        layers = {g: n for g, n, _ in QD.groups(s)}
        total = sum(np.prod(sh) * (layers[g] if g else 1)
                    for _, g, sh, _, _ in QD.leaves(s))
        assert lo < total < hi


def test_decode_keys_and_work():
    # prompt 100, outputs 3..5 produced by feeding positions 102..104,
    # attending 103, 104, 105 keys
    assert list(WK.decode_keys(100, 3, 6)) == [103, 104, 105]
    assert list(WK.decode_keys(100, 0, 2)) == [101]   # output 0 is prefill's
    s = spec("qwen2.5-3b")
    w = QD.decode_attn(s, np.array([103, 104, 105]))
    assert w.flops == 4 * 36 * 16 * 128 * 312
    assert w.bytes == 36 * (2 * 2 * 128 * 2 * 312 + 2 * 16 * 128 * 2 * 3)


def test_prefill_work_by_hand():
    s = spec("qwen3-32b-8l")
    # rows 512..1023 seeded past a 512-token prefix, one 512-row chunk
    w = QD.prefill_attn(s, 512, 1024, 512)
    keys = np.arange(513, 1025).sum()
    assert w.flops == 4 * 8 * 64 * 128 * keys
    assert w.bytes == 8 * (2 * 8 * 128 * 2 * 1024 + 2 * 64 * 128 * 2 * 512)
    # two chunks read the rows up to each chunk's end
    w2 = QD.prefill_attn(s, 0, 1024, 512)
    assert w2.bytes == 8 * (2 * 8 * 128 * 2 * (512 + 1024)
                            + 2 * 64 * 128 * 2 * 1024)


def test_model_flops_by_hand():
    s = spec("qwen2.5-3b")
    f = QD.model_flops(s, np.array([10, 11]), 1)
    assert f == (2 * 36 * QD.layer_matmul_params(s) * 2
                 + 4 * 36 * 16 * 128 * 21 + 2 * 2048 * 151936)


@pytest.mark.parametrize("flops,byt,bound", [(197e12, 1.0, "compute"),
                                             (1.0, 819e9, "memory")])
def test_least_time(flops, byt, bound):
    t, b = WK.least_time(WK.Work(flops, byt), 197e12, 819e9)
    assert b == bound and t == pytest.approx(1.0)
