"""The comparison that decides ``correct`` catches a broken timed path:
each fault a serving cell can have (``bench/faults.py``), planted under
the engine, turns ``correct`` false in an otherwise whole run (tiny
cell, CPU)."""
import numpy as np
import pytest

from bench import faults as F
from bench.tests import tiny


@pytest.mark.parametrize("fault", [F.stale_state, F.half_batch,
                                   F.altered_token],
                         ids=["state-unchanged", "half-batch", "token-altered"])
def test_fault_makes_the_run_incorrect(monkeypatch, fault):
    fault(monkeypatch)
    res, printed = tiny.run(monkeypatch, "tiny.reasoning", 21)
    gap = res["checks"]["logit_gap"]
    assert res["correct"] is False, printed
    assert gap["value"] is None or gap["value"] > gap["limit"]
    assert np.isfinite(res["metrics"]["output_tok_s"]["value"])
