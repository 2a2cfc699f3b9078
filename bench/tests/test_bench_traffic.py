"""The traffic generator: deterministic by seed, lengths within the mix's
clips, and the same work for every seed."""
import numpy as np
import pytest

from bench import traffic as T

MIXES = ["chat", "reasoning"]
BIG_SEED = 2 ** 31 + 12345


def _gen(name, seed, n=300, window_s=0.0):
    mix = T.load_mix(name)
    return mix, T.generate(mix, seed, n, vocab=151936, window_s=window_s)


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_by_seed(name):
    _, a = _gen(name, BIG_SEED)
    _, b = _gen(name, BIG_SEED)
    _, c = _gen(name, BIG_SEED + 1)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.at == y.at for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_clips(name):
    mix, items = _gen(name, 3)
    pre = mix.get("system_prompts", {}).get("tokens", 0)
    p = mix["prompt_tokens"]
    o = mix["output_tokens"]
    stagger = mix.get("stagger_first_round")
    for i, it in enumerate(items):
        assert p["min"] + pre <= len(it.prompt) <= p["max"] + pre
        lo = 1 if stagger and i < mix["clients"] else o["min"]
        assert lo <= it.max_new <= o["max"]
        assert it.prompt.dtype == np.int32
        assert 0 <= it.prompt.min() and it.prompt.max() < 151936
    assert len(items[0].prompt) <= T.max_prompt(mix)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_runs_the_same_work(name):
    """Sizes and gaps are a template put in another order by the seed."""
    _, a = _gen(name, 1)
    _, b = _gen(name, 999999937)
    key = (lambda it: (len(it.prompt), it.max_new)) if name == "chat" else (
        lambda it: len(it.prompt))
    assert sorted(map(key, a)) == sorted(map(key, b))
    if name == "chat":
        assert [it.at for it in a] == [it.at for it in b]


def test_the_window_holds_the_same_work_for_every_seed():
    mix = T.load_mix("chat")
    wins = []
    for seed in (1, 2 ** 31 + 7, 424242):
        items = T.generate(mix, seed, 300, vocab=1000, window_s=51.0)
        win = [it for it in items if it.at < 51.0]
        wins.append((sorted((len(it.prompt), it.max_new) for it in win),
                     round(max(it.at for it in win), 9)))
    assert wins[0] == wins[1] == wins[2]
    assert len(wins[0][0]) > 20


def test_open_loop_is_poisson_at_the_rate():
    mix = T.load_mix("chat")
    mix["rate_per_s"] = 4.0
    items = T.generate(mix, 5, 4000, vocab=1000)
    rate = len(items) / items[-1].at
    assert 3.8 < rate < 4.2
    assert all(b.at >= a.at for a, b in zip(items, items[1:]))


def test_shared_system_prompts():
    mix, items = _gen("chat", 8)
    heads = {it.prompt[:mix["system_prompts"]["tokens"]].tobytes()
             for it in items}
    assert 1 < len(heads) <= mix["system_prompts"]["count"]


def test_closed_loop_first_round_is_staggered():
    mix, items = _gen("reasoning", 4)
    c = mix["clients"]
    assert min(it.max_new for it in items[:c]) < mix["output_tokens"]["min"]


def test_cell_file_overrides_the_mix():
    mix = T.load_mix("chat", "qwen2.5-3b.chat")
    base = T.load_mix("chat")
    assert mix["prompt_tokens"] == base["prompt_tokens"]
    assert mix["rate_per_s"] > 0
