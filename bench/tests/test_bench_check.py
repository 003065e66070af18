"""The comparison that decides ``correct``, at a size the CPU holds.

A tiny qwen2-style cell runs the whole harness (weights from the seed,
the packed served path, warm-up, a closed-loop window, the reference
check), skipping only the look for a chip. A sound run is correct; the
int8 control, put beside it, reads a gap over the limit; and each fault
the cell can have, planted in the timed path's decode step, makes
``correct`` come out false.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import harness
import reference

DATA = Path(__file__).resolve().parent / "data"
CELL = "tiny.docs"
SEED = 2**31 + 17


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    spec = {
        "configs": [{"name": "tiny", "file": str(DATA / "tiny.json")}],
        "workloads": [{"name": CELL, "config": "tiny", "traffic": "tiny_closed", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"}],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run(root):
    return harness.run(CELL, SEED, 1.0, False, require_chip=False, root=root, traffic_dir=DATA)


def test_sound_run_is_correct_and_the_control_is_not(root, monkeypatch):
    readings = {}
    gaps = reference.Checker.gaps

    def with_control(self, prompts, served, control=False):
        readings.update(gaps(self, prompts, served, control=True))
        return readings

    monkeypatch.setattr(reference.Checker, "gaps", with_control)
    result = run(root)
    limit = result["check"]["served_logit_gap"]["limit"]
    assert result["correct"], result["check"]
    assert result["failed"] == 0 and result["attempted"] > 12
    assert readings["served_gap"] <= limit < readings["control_gap"]
    assert result["metrics"]["tokens_per_s"]["value"] > 0


def _state_unchanged(real):
    def decode_step(p, token, pos, caches, cfg, engine=None):
        logits, _ = real(p, token, pos, caches, cfg, engine=engine)
        return logits, caches
    return decode_step


def _half_batch(real):
    def decode_step(p, token, pos, caches, cfg, engine=None):
        logits, new = real(p, token, pos, caches, cfg, engine=engine)
        h = token.shape[0] // 2
        logits = jnp.concatenate([logits[:h], logits[: token.shape[0] - h]])
        new = jax.tree.map(lambda n, o: n.at[:, h:].set(o[:, h:]), new, caches)
        return logits, new
    return decode_step


def _token_altered(real):
    def decode_step(p, token, pos, caches, cfg, engine=None):
        logits, new = real(p, token, pos, caches, cfg, engine=engine)
        # every third position emits the worst token instead of the best
        worst = jnp.where(logits > -1e29, logits, jnp.inf).argmin(-1)
        best = logits.argmax(-1)
        rows = jnp.arange(logits.shape[0])
        hit = (pos % 3 == 0)[:, None]
        swapped = logits.at[rows, worst].set(logits[rows, best] + 1.0)
        return jnp.where(hit, swapped, logits), new
    return decode_step


@pytest.mark.parametrize(
    "fault", [_state_unchanged, _half_batch, _token_altered],
    ids=["state_unchanged", "half_batch", "token_altered"],
)
def test_a_fault_in_the_timed_path_is_not_correct(root, monkeypatch, fault):
    from repro.models import lm

    monkeypatch.setattr(lm, "decode_step", fault(lm.decode_step))
    result = run(root)
    assert not result["correct"], result["check"]
