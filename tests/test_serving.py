"""Continuous batching must be semantically invisible: any interleaving
of requests produces the same tokens as running each alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compiler as compiler_lib
from repro.configs import get_smoke_config
from repro.models import lm as lm_lib
from repro.serving import Request


def _compiled(cfg, params):
    return compiler_lib.compile(
        cfg, params, compiler_lib.HardwareTarget(engine="reference")
    )


def _reference_generate(cfg, params, prompt, n_new):
    """Isolated greedy generation via prefill + per-token decode."""
    tokens = jnp.asarray(prompt, jnp.int32)[None, :]
    logits, pre = lm_lib.prefill(params, tokens, cfg)
    caches = lm_lib.init_cache(cfg, 1, 64)
    caches = jax.tree.map(
        lambda d, s: d.at[:, :, : s.shape[2]].set(s.astype(d.dtype))
        if d.ndim == 5 and d.shape[2] >= s.shape[2]
        else s.astype(d.dtype),
        caches, pre,
    )
    out = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    tok = jnp.asarray([out[-1]], jnp.int32)
    for _ in range(n_new - 1):
        logits, caches = lm_lib.decode_step(params, tok, jnp.asarray(pos), caches, cfg)
        out.append(int(jnp.argmax(logits[0])))
        tok = jnp.asarray([out[-1]], jnp.int32)
        pos += 1
    return out


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-2.7b"])
def test_continuous_batching_matches_isolated(arch):
    cfg = get_smoke_config(arch)
    params = lm_lib.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 9, 7)]
    n_new = 6

    refs = [_reference_generate(cfg, params, p, n_new) for p in prompts]

    # 3 requests, only 2 slots: forces queueing + slot reuse
    eng = _compiled(cfg, params).serve(max_batch=2, max_len=64)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n_new) for i, p in enumerate(prompts)]
    states = [eng.submit(reqs[0]), eng.submit(reqs[1])]
    eng.step()          # tick 1: both admitted
    states.append(eng.submit(reqs[2]))  # arrives mid-flight
    done = eng.run_to_completion()

    assert len(done) == 3 and all(s.done for s in states)
    for st, ref in zip(states, refs):
        assert st.generated == ref, (
            f"req {st.rid}: continuous batching changed the output\n"
            f"  batched:  {st.generated}\n  isolated: {ref}"
        )


def test_slots_free_and_reuse():
    cfg = get_smoke_config("tinyllama-1.1b")
    params = lm_lib.init_params(jax.random.key(1), cfg)
    eng = _compiled(cfg, params).serve(max_batch=1, max_len=32)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, 100, size=4).astype(np.int32),
                    max_new_tokens=3) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    done = eng.run_to_completion()
    assert [r.rid for r in done] == [0, 1, 2]  # sequential through 1 slot
    assert all(len(r.generated) == 3 for r in done)


def test_gathered_ticks_counts_partial_pool_ticks():
    """The dispatch rule: a plan whose lanes cover the pool decodes it in
    place (``"full"`` with every slot live, ``"masked"`` with some free,
    counted in ``masked_ticks``); only a plan with fewer lanes than slots
    runs the gather/scatter program, counted in ``gathered_ticks``."""
    from repro import obs

    cfg = get_smoke_config("tinyllama-1.1b")
    params = lm_lib.init_params(jax.random.key(2), cfg)
    eng = _compiled(cfg, params).serve(max_batch=2, max_len=32)
    assert eng.group_k == 2  # K = pool: every plan covers the pool
    rng = np.random.default_rng(2)
    prompt = lambda: rng.integers(0, 100, size=4).astype(np.int32)  # noqa: E731
    # tick 0: both slots live (full); then request 1 alone for 3 ticks
    eng.submit(Request(rid=0, prompt=prompt(), max_new_tokens=2))
    eng.submit(Request(rid=1, prompt=prompt(), max_new_tokens=5))
    with obs.session() as tel:
        eng.run_to_completion()
    s = eng.stats()
    assert (s.ticks, s.gathered_ticks, s.masked_ticks) == (4, 0, 3)
    paths = [sp.attrs["path"] for sp in tel.tracer.spans("decode_tick")]
    assert paths == ["full", "masked", "masked", "masked"]
    assert [sp.attrs["n_pad"] for sp in tel.tracer.spans("decode_tick")] == [0, 1, 1, 1]
    # a later full tick leaves the counts alone; a partial one adds one
    eng.submit(Request(rid=2, prompt=prompt(), max_new_tokens=2))
    eng.submit(Request(rid=3, prompt=prompt(), max_new_tokens=2))
    eng.run_to_completion()
    assert (eng.stats().ticks, eng.stats().masked_ticks) == (5, 3)
    eng.submit(Request(rid=4, prompt=prompt(), max_new_tokens=3))
    eng.run_to_completion()
    s = eng.stats()
    assert (s.ticks, s.gathered_ticks, s.masked_ticks) == (7, 0, 5)

    # K = 2 below a pool of 4: one live slot plans 2 lanes and gathers
    # them; three live slots plan 4 lanes, which cover the pool
    eng = compiler_lib.compile(
        cfg, params, compiler_lib.HardwareTarget(engine="reference", group_size=2)
    ).serve(max_batch=4, max_len=32)
    eng.submit(Request(rid=5, prompt=prompt(), max_new_tokens=3))
    with obs.session() as tel:
        eng.run_to_completion()
    for i in range(3):
        eng.submit(Request(rid=6 + i, prompt=prompt(), max_new_tokens=2))
    with obs.session() as tel2:
        eng.run_to_completion()
    paths = [sp.attrs["path"] for t in (tel, tel2) for sp in t.tracer.spans("decode_tick")]
    assert paths == ["gathered", "gathered", "masked"]
    s = eng.stats()
    assert (s.ticks, s.gathered_ticks, s.masked_ticks) == (3, 2, 1)


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-2.7b", "qwen3-moe-235b-a22b"])
def test_dead_lanes_leave_admitted_and_restored_slots_exact(arch):
    """With K = pool every partial tick decodes the whole pool in place,
    free slots riding along as dead lanes. A request admitted into a slot
    that decoded dead for several ticks, and a preempted one restored into
    such a slot, each generate exactly the tokens they generate alone."""
    from repro.serving import SchedulerConfig

    cfg = get_smoke_config(arch)
    params = lm_lib.init_params(jax.random.key(3), cfg)
    cm = _compiled(cfg, params)
    rng = np.random.default_rng(3)
    # name: (prompt length, new tokens, priority)
    sizes = {"a": (5, 20, 0), "b": (9, 8, 0), "h": (7, 4, 5), "w": (3, 2, 5)}
    reqs = {
        name: Request(
            rid=i, prompt=rng.integers(1, cfg.vocab_size, size=n).astype(np.int32),
            max_new_tokens=g, priority=prio,
        )
        for i, (name, (n, g, prio)) in enumerate(sizes.items())
    }
    alone = {}
    for name, req in reqs.items():
        se = cm.serve(max_batch=1, max_len=32)
        st = se.submit(req)
        se.drain()
        alone[name] = st.generated

    # KV budget 3 x 32 = 96 rows, 45 usable: a + b + h (24 + 16 + 10)
    # overflows it, a + w + b (24 + 4 + 16) fits
    se = cm.serve(max_batch=3, max_len=32,
                  scheduler=SchedulerConfig(kv_reserve_ratio=0.53))
    assert se.group_k == 3 and se.scheduler.kv_usable == 45
    running = se.scheduler.running
    st = {"a": se.submit(reqs["a"])}
    for _ in range(3):
        se.step()                       # slots 1 and 2 decode as dead lanes
    st["b"] = se.submit(reqs["b"])
    se.step()
    se.step()
    assert running[1] is st["b"]        # admitted into a dead-lane slot
    st["h"] = se.submit(reqs["h"])      # evicts b, takes slot 1
    while not st["h"].done:
        se.step()
    assert st["b"].preemptions == 1 and set(running) == {0}
    st["w"] = se.submit(reqs["w"])
    se.step()                           # w into slot 1, b restored into slot 2
    assert running[2] is st["b"]
    se.drain()
    s = se.stats()
    assert (s.evictions, s.restores, s.gathered_ticks) == (1, 1, 0)
    assert s.masked_ticks >= 8
    for name in reqs:
        assert st[name].generated == alone[name], name
