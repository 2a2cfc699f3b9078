"""Faults planted under the engine, to show that ``correct`` catches them.

    python3 bench/faults.py <fault> --workload <cell> --seed <n> --seconds <s> --trace 0

plants one fault in the program, in this process only, and then runs the
cell as ``bench/run.py`` would; the run has to print ``"correct": false``.
The CPU tests (``bench/tests/test_bench_faults.py``) plant the same faults
at a test size.  Each planter takes an object with a pytest-style
``setattr(target, name, value)``.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def stale_state(mp) -> None:
    """The decode step returns its KV state unchanged."""
    from repro.models import transformer
    real = transformer.decode_step

    def step(cfg, params, tokens, cache, **kw):
        logits, _ = real(cfg, params, tokens, cache, **kw)
        return logits, cache
    mp.setattr(transformer, "decode_step", step)


def half_batch(mp) -> None:
    """The decode step leaves out the second half of the batch: those
    slots get the first half's logits."""
    from repro.models import transformer
    real = transformer.decode_step

    def step(cfg, params, tokens, cache, **kw):
        logits, new = real(cfg, params, tokens, cache, **kw)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:h]), new
    mp.setattr(transformer, "decode_step", step)


def altered_token(mp) -> None:
    """Every fifth sampled token is altered where it is produced."""
    from repro.serving.engine import ServingEngine
    real = ServingEngine._sample_active
    count = [0]

    def sample(self, active):
        toks = real(self, active)
        for slot in toks:
            count[0] += 1
            if count[0] % 5 == 0:
                toks[slot] = (toks[slot] + 1) % self.cfg.vocab_size
        return toks
    mp.setattr(ServingEngine, "_sample_active", sample)


FAULTS = {"state-unchanged": stale_state, "half-batch": half_batch,
          "token-altered": altered_token}


class _Patch:
    @staticmethod
    def setattr(target, name, value) -> None:
        setattr(target, name, value)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in FAULTS:
        print(f"usage: faults.py {{{','.join(FAULTS)}}} <run.py arguments>",
              file=sys.stderr)
        return 2
    FAULTS[argv[0]](_Patch)
    from bench import run
    return run.main(argv[1:])


if __name__ == "__main__":
    raise SystemExit(main())
