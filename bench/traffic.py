"""The one traffic generator: reads a mix from ``traffic/<name>.json``.

A mix file holds only parameters:

* ``loop``: ``"open"`` (independent users: Poisson arrivals at
  ``rate_per_s``) or ``"closed"`` (``clients`` callers, each sending
  its next request when the last one finished);
* ``prompt_lens`` / ``output_lens``: ``{length: count}`` per block of
  ``block`` requests. Every block holds exactly these counts, each
  shuffled by the seed, so any seed gives the same set of sizes in
  another order and a run's work does not depend on its seed;
* ``max_batch`` / ``max_len``: the slot pool the mix is served from;
* ``check_requests``: how many served requests the reference checks;
* optional ``shared_prefixes``: ``{"count": k, "length": n}``: every
  prompt is one of ``k`` shared ``n``-token system prompts (each used
  equally often, in an order drawn from the seed) followed by a user
  turn whose length ``prompt_lens`` gives;
* optional ``replicas`` and ``routing``: serve from that many one-chip
  replicas behind the fleet router instead of one slot pool;
* optional ``schedule_seed``: draw the sizes and the arrival order
  from this fixed seed instead of the run's, so that every run offers
  one schedule and the run's seed changes only the token ids (and the
  weights): a tail over a hundred requests near the knee otherwise
  swings by a factor of two with the order alone;
* optional ``warm_requests``: after the shape warm-up, that many
  requests of the mix itself (another seed stream) are served before
  the window, to reach the shapes only the traffic finds (prefix
  grafts behind a router).

Arrivals of an open loop: each block's gaps are the ``block``
quantiles of the exponential distribution, shuffled by the seed, all
scaled so that the requests span exactly the window. Every seed thus
gets the same gaps, and every block the same work over the same time,
in another order. Prompt token ids are drawn per request from the seed.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str, directory: Path | None = None) -> dict:
    path = (directory or HERE / "traffic") / f"{name}.json"
    mix = json.loads(path.read_text())
    for key in ("prompt_lens", "output_lens"):
        if sum(mix[key].values()) != mix["block"]:
            raise ValueError(f"{path}: {key} counts do not sum to block={mix['block']}")
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed', got {mix['loop']!r}")
    return mix


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, *stream])


def _counts(table: dict) -> np.ndarray:
    return np.concatenate([np.full(n, int(length)) for length, n in table.items()])


def sizes(mix: dict, seed: int, n_blocks: int) -> list[tuple[int, int]]:
    """(prompt_len, max_new_tokens) of ``n_blocks * block`` requests."""
    rng = _rng(mix.get("schedule_seed", seed), 0)
    prompts, outputs = _counts(mix["prompt_lens"]), _counts(mix["output_lens"])
    out = []
    for _ in range(n_blocks):
        out += list(zip(rng.permutation(prompts).tolist(), rng.permutation(outputs).tolist()))
    return out


def open_loop(mix: dict, seed: int, seconds: float) -> list[tuple[float, int, int]]:
    """(due offset in s, prompt_len, max_new_tokens) of every request of
    an open-loop window of ``seconds``."""
    block = mix["block"]
    n_blocks = max(1, round(mix["rate_per_s"] * seconds / block))
    quantiles = -np.log1p(-(np.arange(block) + 0.5) / block)
    rng = _rng(mix.get("schedule_seed", seed), 1)
    gaps = np.concatenate([rng.permutation(quantiles) for _ in range(n_blocks)])
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [(float(t), p, g) for t, (p, g) in zip(due, sizes(mix, seed, n_blocks))]


def closed_loop_sizes(mix: dict, seed: int, n: int) -> list[tuple[int, int]]:
    """The first ``n`` (prompt_len, max_new_tokens) of a closed loop."""
    return sizes(mix, seed, math.ceil(n / mix["block"]))[:n]


def prompt(seed: int, rid: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of request ``rid``, drawn from the seed (ids 0..vocab-1)."""
    return _rng(seed, 2, rid).integers(0, vocab, length, dtype=np.int32)


def request_prompt(mix: dict, seed: int, rid: int, length: int, vocab: int) -> np.ndarray:
    """Request ``rid``'s prompt: with ``shared_prefixes`` one of the
    shared system prompts (the same share for each over every ``count``
    requests) then a ``length``-token user turn; else ``length`` tokens."""
    turn = prompt(seed, rid, length, vocab)
    shared = mix.get("shared_prefixes")
    if not shared:
        return turn
    k = shared["count"]
    which = int(_rng(seed, 3, rid // k).permutation(k)[rid % k])
    system = _rng(seed, 4, which).integers(0, vocab, shared["length"], dtype=np.int32)
    return np.concatenate([system, turn])


def prompt_lengths(mix: dict) -> list[int]:
    """Every whole prompt length the mix sends."""
    extra = mix.get("shared_prefixes", {}).get("length", 0)
    return sorted(int(p) + extra for p in mix["prompt_lens"])
