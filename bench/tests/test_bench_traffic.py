"""The traffic generator: one seed, one schedule; every seed, the same
set of sizes and gaps in another order."""

from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import traffic

MIXES = ["chat", "docs"]


@pytest.mark.parametrize("name", MIXES)
def test_mix_files_load(name):
    mix = traffic.load(name)
    assert mix["max_len"] >= max(map(int, mix["prompt_lens"])) + max(map(int, mix["output_lens"]))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_open_loop_is_deterministic_per_seed(seed):
    mix = traffic.load("chat")
    assert traffic.open_loop(mix, seed, 30.0) == traffic.open_loop(mix, seed, 30.0)


def test_open_loop_seeds_share_sizes_and_gaps():
    mix = {k: v for k, v in traffic.load("chat").items() if k != "schedule_seed"}
    a, b = traffic.open_loop(mix, 1, 30.0), traffic.open_loop(mix, 2**31 + 9, 30.0)
    assert a != b
    assert sorted(p for _, p, _ in a) == sorted(p for _, p, _ in b)
    assert sorted(g for _, _, g in a) == sorted(g for _, _, g in b)
    # the due times' gaps are one set in another order (but for the
    # last gap, which falls after the last request)
    gaps = lambda s: Counter(np.round(np.diff([t for t, _, _ in s]), 6))  # noqa: E731
    assert sum((gaps(a) - gaps(b)).values()) <= 1
    assert all(0.0 <= t < 30.0 for t, _, _ in a)


def test_open_loop_keeps_the_mix_per_block():
    mix = traffic.load("chat")
    plan = traffic.open_loop(mix, 3, 30.0)
    assert len(plan) % mix["block"] == 0
    n_blocks = len(plan) // mix["block"]
    got = Counter(p for _, p, _ in plan)
    assert got == Counter({int(k): v * n_blocks for k, v in mix["prompt_lens"].items()})
    assert len(plan) / 30.0 == pytest.approx(mix["rate_per_s"], rel=0.1)


def test_closed_loop_prefixes_repeat():
    mix = traffic.load("docs")
    assert traffic.closed_loop_sizes(mix, 5, 40) == traffic.closed_loop_sizes(mix, 5, 100)[:40]
    assert traffic.closed_loop_sizes(mix, 5, 40) != traffic.closed_loop_sizes(mix, 6, 40)


def test_prompts_are_per_request_and_in_vocab():
    a = traffic.prompt(11, 3, 128, 19008)
    assert (a == traffic.prompt(11, 3, 128, 19008)).all()
    assert not (a == traffic.prompt(11, 4, 128, 19008)).all()
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 19008


def test_bad_counts_are_refused(tmp_path: Path):
    (tmp_path / "bad.json").write_text(
        '{"loop": "open", "block": 4, "prompt_lens": {"8": 3}, "output_lens": {"4": 4}}'
    )
    with pytest.raises(ValueError, match="block"):
        traffic.load("bad", tmp_path)


def test_schedule_seed_fixes_the_schedule_not_the_tokens():
    mix = dict(traffic.load("chat"), schedule_seed=0)
    assert traffic.open_loop(mix, 1, 47.0) == traffic.open_loop(mix, 2**31 + 9, 47.0)
    assert not (traffic.request_prompt(mix, 1, 0, 128, 151936)
                == traffic.request_prompt(mix, 2, 0, 128, 151936)).all()


def test_shared_prefixes_are_used_evenly():
    mix = {"shared_prefixes": {"count": 4, "length": 16}}
    heads = [tuple(traffic.request_prompt(mix, 5, rid, 8, 1000)[:16]) for rid in range(40)]
    assert len(set(heads)) == 4
    assert sorted(Counter(heads).values()) == [10, 10, 10, 10]
    assert len(traffic.request_prompt(mix, 5, 0, 8, 1000)) == 24
