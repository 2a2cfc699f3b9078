"""A sound tiny run's served tokens sit within rounding of the float32
reference, and the fp8 control, judged in the program's place at the
same positions, comes out not correct."""
import re

import numpy as np
import pytest

from bench import check as CK
from bench.tests import tiny


def test_sound_run_is_correct_and_the_control_is_not(monkeypatch):
    res, printed = tiny.run(monkeypatch, "tiny.chat", 3000000001,
                            control=True)
    gap = res["checks"]["logit_gap"]
    program = float(re.search(r"served tokens, .*widest gap ([0-9.e-]+),",
                              printed).group(1))
    control = float(re.search(r"control fp8: widest gap ([0-9.e-]+)",
                              printed).group(1))
    assert 0 <= program <= gap["limit"], printed
    assert gap["value"] == control > gap["limit"], printed
    assert res["correct"] is False, printed
    assert res["failed"] == 0 and res["attempted"] > 5
    assert "compiles inside the window: 0 " in printed
    assert set(res["metrics"]) == {"ttft_p50_ms", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"


def test_sound_closed_loop_run_is_correct(monkeypatch):
    res, printed = tiny.run(monkeypatch, "tiny.reasoning", 2 ** 31 + 77)
    gap = res["checks"]["logit_gap"]
    assert res["correct"] is True, printed
    assert 0 <= gap["value"] <= gap["limit"]
    assert "window opens: set-up" in printed
    assert res["metrics"]["output_tok_s"]["value"] > 0


GAPS = np.array([0.0] * 16 + [0.01, 0.2, 0.3, 0.5])


@pytest.mark.parametrize("name,value", [
    ("logit_gap", 0.5), ("gap_p50", 0.0), ("gap_p90", 0.2 + 0.1 * 0.1),
    ("gap_p95", 0.31)])
def test_compared_numbers(name, value):
    """Each number a configuration's ``check`` stanza may name, read from
    the same gaps; a dense stanza compares the widest gap alone."""
    assert CK.number(name)(GAPS) == pytest.approx(value, abs=1e-12)
    assert CK.limits({"check": {f"{name}_limit": 1.0, "why": "-"}}) == {
        name: 1.0}
    assert CK.limits(tiny.conf()) == {"logit_gap": 0.05}
    for bad in ("gap_p0", "gap_p100", "mean_gap", "why"):
        with pytest.raises(ValueError):
            CK.limits({"check": {f"{bad}_limit": 1.0}})
