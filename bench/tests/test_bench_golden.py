"""What the benchmark reads for the dense configurations, pinned bit for
bit to the values the harness gave before its architecture-specific parts
moved behind ``bench/archs/`` (``data/golden_dense.json``): the seeded
weight tree of the tiny configuration, the tree shapes of both dense
configurations, the work of fixed trace snapshots, and the reduction of
the recorded v5e trace.

The reference's gaps for one fixed sample are pinned within rounding,
not bit for bit: XLA's CPU matmul sums in another order when the process
has one CPU than when it has several, and moves the last digits (by
1.7e-6 on gaps up to 5.1, about three float32 steps).  Which gaps are 0,
the served token or the control's pick being the reference's argmax,
does not depend on that order and is pinned exactly."""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import archs
from bench import reference as R
from bench import run as RUN
from bench import trace_reduce as TR
from bench import weights as W
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
GOLD = json.loads((DATA / "golden_dense.json").read_text())


def conf(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def flat(tree):
    return [[jax.tree_util.keystr(p), list(x.shape), str(x.dtype)]
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]]


def test_tiny_weight_tree_is_bit_identical():
    c = tiny.conf()
    arch = archs.of(c)
    s = arch.spec_of(c)
    gold = GOLD["tiny_tree"]
    tree = jax.jit(lambda k: W.make_tree(arch, s, k, jnp.bfloat16))(
        W.root_key(gold["seed"]))
    h = hashlib.sha256()
    for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(p).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    assert flat(tree) == gold["leaves"]
    wq = np.asarray(tree["blocks"]["attn"]["wq"], np.float32).reshape(-1)
    assert [float(v) for v in wq[:4]] == gold["wq0"]
    assert h.hexdigest() == gold["sha256"]


@pytest.mark.parametrize("name", ["qwen2.5-3b", "qwen3-32b-8l"])
def test_config_tree_shapes(name):
    c = conf(name)
    arch = archs.of(c)
    s = arch.spec_of(c)
    t = jax.eval_shape(lambda k: W.make_tree(arch, s, k, jnp.dtype(s["dtype"])),
                       W.root_key(0))
    assert flat(t) == GOLD[f"tree:{name}"]


def assert_gaps(got, gold):
    got, gold = np.asarray(got, np.float64), np.asarray(gold, np.float64)
    assert got.shape == gold.shape
    assert ((got == 0) == (gold == 0)).all()
    # a few float32 steps at the sample's widest gap, the logits' scale
    np.testing.assert_allclose(got, gold, rtol=0,
                               atol=2e-6 * max(GOLD["served_gaps"]))


def test_reference_gaps_are_bit_identical():
    """Bit for bit as far as the summation order allows (see above)."""
    smp_in = GOLD["sample"]
    smp = R.Sample([np.asarray(p, np.int32) for p in smp_in["prompts"]],
                   smp_in["outputs"], smp_in["seq_len"], smp_in["n_out"])
    c = tiny.conf()
    gaps = R.served_gaps(c, smp_in["seed"], smp)
    program, control = R.control_gaps(c, smp_in["seed"], smp)
    assert_gaps(gaps, GOLD["served_gaps"])
    assert_gaps(program, GOLD["control_gaps"][0])
    assert_gaps(control, GOLD["control_gaps"][1])


def _snaps():
    return [{"t": a["t"], "prefill_rows": a["prefill_rows"],
             "reqs": {int(k): tuple(v) for k, v in a["reqs"].items()}}
            for a in GOLD["span_snaps"]]


@pytest.mark.parametrize("name", ["qwen2.5-3b", "qwen3-32b-8l"])
def test_span_work_is_bit_identical(name):
    c = conf(name)
    arch = archs.of(c)
    w = RUN.span_work(arch, arch.spec_of(c), _snaps(), 16, 512)
    got = {k: ([v.flops, v.bytes] if hasattr(v, "flops") else v)
           for k, v in w.items()}
    assert got == GOLD[f"span_work:{name}"]


@pytest.mark.parametrize("skip", [("bench.trace_span",),
                                  ("bench.trace_span", "bench.wait")],
                         ids=["skip_span", "skip_wait"])
def test_trace_reduction_is_bit_identical(skip):
    events = TR.load_events(DATA / "trace_v5e.json")
    span = TR.find_span(events, "bench.trace_span")
    r = TR.reduce(events, span, 1, archs.of(tiny.conf()).KERNELS,
                  skip_host=skip)
    gold = GOLD["reduce:" + ("skip_span" if len(skip) == 1 else "skip_wait")]
    assert {"window_s": r.window_s, "busy_s": r.busy_s, "chips": r.chips,
            "op_s": r.op_s, "kernel_s": r.kernel_s, "program_s": r.program_s,
            "gaps": r.gaps} == gold
