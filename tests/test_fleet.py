"""Fleet serving (PR 10): the prefix-affinity router, prefix-grafted
continuation prefill, the replica pool's failover path — and the one
invariant that matters one level up from the scheduler's: no routing
policy, replica count, prefix graft or mid-serve failover may change a
request's generated tokens vs running it alone on one replica.

Also covers the PR 10 satellites: cross-pool ``SlotSnapshot``
portability, end-to-end request latency on ``RequestState``, the
scheduler's ``adopt`` seam, ``costmodel.fleet_price`` and the
``benchmarks.run`` section-listing CLI.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro import compiler as compiler_lib
from repro.configs import get_smoke_config
from repro.fleet import (
    FleetEngine,
    FleetRouter,
    PrefixIndex,
    Replica,
    RoutingConfigError,
    chain_hashes,
)
from repro.models import layers, lm as lm_lib
from repro.serving import (
    PrefixGraft,
    Request,
    RequestStatus,
    SlotSnapshot,
)

MAX_LEN = 40
GEN = 4
BLOCK = 4


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(get_smoke_config("tinyllama-1.1b"), quant="bnn")
    params = lm_lib.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, (2 * BLOCK,), dtype=np.int32)
    prompts = []
    for i in range(6):
        if i % 2 == 0:   # half share one block-aligned prefix
            tail = rng.integers(1, cfg.vocab_size, (2 + i % 3,), np.int32)
            prompts.append(np.concatenate([shared, tail]))
        else:
            prompts.append(rng.integers(1, cfg.vocab_size, (5,), np.int32))
    return cfg, params, prompts


@pytest.fixture(scope="module")
def compiled(model):
    cfg, params, _ = model
    return {
        name: compiler_lib.compile(
            cfg, params, compiler_lib.HardwareTarget(engine=name)
        )
        for name in ("reference", "packed")
    }


@pytest.fixture(scope="module")
def solo(model, compiled):
    """Per-request reference generations: each alone in a 1-slot pool."""
    _, _, prompts = model
    out = {}
    for name, cm in compiled.items():
        for i, p in enumerate(prompts):
            se = cm.serve(max_batch=1, max_len=MAX_LEN)
            st = se.submit(Request(rid=i, prompt=p, max_new_tokens=GEN))
            se.drain()
            out[(name, i)] = tuple(st.generated)
    return out


def _drive_staggered(fleet, prompts, gen=GEN):
    """One submit per fleet tick (the prefix library fills as later
    requests arrive), then drain."""
    states = []
    for i, p in enumerate(prompts):
        states.append(
            fleet.submit(Request(rid=i, prompt=p, max_new_tokens=gen))
        )
        fleet.step()
    fleet.drain()
    return states


# ---------------------------------------------------------------------------
# router units (no model involved)
# ---------------------------------------------------------------------------


class TestChainHashes:
    def test_chained_prefix_identity(self):
        a = np.arange(100, 116, dtype=np.int32)
        b = np.concatenate([a[:8], np.arange(900, 908, dtype=np.int32)])
        ha, hb = chain_hashes(a, 4), chain_hashes(b, 4)
        # identical first two blocks -> identical first two chain links;
        # divergence at block 2 changes every later link
        assert ha[:2] == hb[:2]
        assert ha[2:] != hb[2:]

    def test_partial_block_unhashed(self):
        toks = np.arange(10, dtype=np.int32)
        assert len(chain_hashes(toks, 4)) == 2
        assert len(chain_hashes(toks[:3], 4)) == 0

    def test_chain_covers_prefix_not_content(self):
        # same block content at a different chain position hashes
        # differently (the chain carries position)
        a = np.array([1, 2, 3, 4, 1, 2, 3, 4], np.int32)
        h = chain_hashes(a, 4)
        assert h[0] != h[1]


class TestPrefixIndex:
    def test_match_exceeds_block_boundary(self):
        idx = PrefixIndex(block_size=4)
        donor = np.arange(10, dtype=np.int32)
        idx.insert(donor, rows="rows")
        entry, common = idx.match(np.arange(9, dtype=np.int32))
        assert entry is not None
        assert common == 9      # element-wise, past the last full block

    def test_no_match_below_one_block(self):
        idx = PrefixIndex(block_size=4)
        idx.insert(np.arange(8, dtype=np.int32), rows=None)
        query = np.concatenate([
            np.arange(2, dtype=np.int32),
            np.full((6,), 999, np.int32),
        ])
        entry, common = idx.match(query)
        assert entry is None and common == 0

    def test_lru_eviction_bounds_entries(self):
        idx = PrefixIndex(block_size=2, capacity=2)
        for base in (0, 100, 200):
            idx.insert(np.arange(base, base + 4, dtype=np.int32), rows=base)
        assert len(idx) == 2
        # the oldest donor is gone; the newest two still match
        assert idx.match(np.arange(0, 4, dtype=np.int32))[0] is None
        assert idx.match(np.arange(200, 204, dtype=np.int32))[0] is not None

    def test_longest_chain_wins_contested_hash(self):
        idx = PrefixIndex(block_size=2)
        idx.insert(np.arange(4, dtype=np.int32), rows="short")
        idx.insert(np.arange(8, dtype=np.int32), rows="long")
        entry, common = idx.match(np.arange(8, dtype=np.int32))
        assert entry.rows == "long" and common == 8

    def test_bad_config(self):
        with pytest.raises(RoutingConfigError, match="block_size"):
            PrefixIndex(block_size=0)
        with pytest.raises(RoutingConfigError, match="capacity"):
            PrefixIndex(block_size=2, capacity=0)


class TestFleetRouter:
    def test_unknown_policy(self):
        with pytest.raises(RoutingConfigError, match="lifo"):
            FleetRouter([0, 1], policy="lifo")

    def test_round_robin_cycles(self):
        r = FleetRouter([0, 1, 2], policy="round-robin")
        toks = np.arange(8, dtype=np.int32)
        loads = {0: 0.0, 1: 0.0, 2: 0.0}
        got = [r.route(toks, loads).replica for _ in range(6)]
        assert got == [0, 1, 2, 0, 1, 2]

    def test_least_loaded_picks_freest(self):
        r = FleetRouter([0, 1], policy="least-loaded")
        d = r.route(np.arange(8, dtype=np.int32), {0: 5.0, 1: 1.0})
        assert d.replica == 1

    def test_prefix_routes_to_library_holder(self):
        r = FleetRouter([0, 1], policy="prefix", block_size=4)
        donor = np.arange(12, dtype=np.int32)
        r.observe_prefill(1, donor, rows="kv")
        # replica 1 holds the prefix but is more loaded — affinity wins
        d = r.route(donor, {0: 0.0, 1: 50.0})
        assert d.replica == 1
        assert d.graft_length == 11     # capped at prompt_len - 1
        assert d.entry.rows == "kv"

    def test_prefix_miss_falls_back_to_load(self):
        r = FleetRouter([0, 1], policy="prefix", block_size=4)
        d = r.route(np.arange(12, dtype=np.int32), {0: 9.0, 1: 2.0})
        assert d.replica == 1 and d.graft_length == 0
        assert r.prefix_hits == 0

    def test_forget_replica_stops_routing_to_it(self):
        r = FleetRouter([0, 1], policy="prefix", block_size=4)
        donor = np.arange(12, dtype=np.int32)
        r.observe_prefill(1, donor, rows="kv")
        r.forget_replica(1)
        d = r.route(donor, {0: 0.0})
        assert d.replica == 0 and d.graft_length == 0


# ---------------------------------------------------------------------------
# prefix-grafted continuation prefill
# ---------------------------------------------------------------------------


class TestPrefillContinue:
    @pytest.mark.parametrize("engine", ["reference", "packed"])
    def test_matches_full_prefill_bitwise(self, model, compiled, engine):
        """The load-bearing numeric invariant: prefilling a suffix over
        donated prefix KV rows reproduces the full prefill's logits AND
        caches bit-for-bit (the suffix goes through the same prefill
        attention graph, and cached rows are prompt-length-invariant)."""
        _, _, prompts = model
        cm = compiled[engine]
        prompt = prompts[0][None, :]
        full_logits, full_caches = cm.prefill(prompt)
        cut = BLOCK
        _, donor = cm.prefill(prompt[:, :cut])
        # compiled as the serving engine compiles its continuation
        cont_logits, cont_caches = jax.jit(
            lambda p, t, pre: lm_lib.prefill_continue(
                p, t, pre, cm.cfg, engine=cm.engine
            ),
            compiler_options=layers.EXACT_COMPILER_OPTIONS,
        )(cm.params, prompt[:, cut:], donor)
        assert (np.asarray(full_logits) == np.asarray(cont_logits)).all()
        for a, b in zip(jax.tree.leaves(full_caches),
                        jax.tree.leaves(cont_caches)):
            assert (np.asarray(a) == np.asarray(b)).all()

    def test_rejects_non_attention_stacks(self):
        ssm_cfg = get_smoke_config("mamba2-2.7b")
        ssm_params = lm_lib.init_params(jax.random.key(0), ssm_cfg)
        toks = np.arange(4, dtype=np.int32)[None, :]
        with pytest.raises(lm_lib.PrefixContinuationError, match="mixer"):
            lm_lib.prefill_continue(ssm_params, toks, {}, ssm_cfg)

    def test_grafted_admission_is_exact_and_counted(self, compiled, model,
                                                    solo):
        """ServingEngine.prefill_into with a PrefixGraft: same tokens,
        fewer prompt tokens prefilled, ledger split between counters."""
        _, _, prompts = model
        cm = compiled["reference"]
        prompt = prompts[0]
        _, donor = cm.prefill(prompt[None, :BLOCK])
        rows = jax.tree.map(lambda c: c[:, 0], donor)

        se = cm.serve(max_batch=1, max_len=MAX_LEN)
        st = se.submit(Request(
            rid=0, prompt=prompt, max_new_tokens=GEN,
            prefix=PrefixGraft(length=BLOCK, rows=rows),
        ))
        se.drain()
        assert tuple(st.generated) == solo[("reference", 0)]
        s = se.stats()
        assert s.grafted_tokens == BLOCK
        assert s.prefill_tokens == len(prompt) - BLOCK


# ---------------------------------------------------------------------------
# the fleet invariant: routed == solo, bit-exact
# ---------------------------------------------------------------------------


class TestFleetExactness:
    @pytest.mark.parametrize("policy",
                             ["prefix", "least-loaded", "round-robin"])
    @pytest.mark.parametrize("n_replicas", [1, 2, 3])
    def test_routed_equals_solo(self, compiled, model, solo, policy,
                                n_replicas):
        _, _, prompts = model
        cm = compiled["reference"]
        fleet = FleetEngine(
            [Replica(r, cm, max_batch=2, max_len=MAX_LEN)
             for r in range(n_replicas)],
            routing=policy, block_size=BLOCK,
        )
        states = _drive_staggered(fleet, prompts)
        for st in states:
            assert st.status is RequestStatus.FINISHED
            assert tuple(st.generated) == solo[("reference", st.request.rid)]
        s = fleet.stats()
        assert s.finished == len(prompts) and s.failed == 0

    def test_packed_engine_with_grafts(self, compiled, model, solo):
        _, _, prompts = model
        cm = compiled["packed"]
        fleet = FleetEngine(
            [Replica(r, cm, max_batch=2, max_len=MAX_LEN) for r in range(2)],
            routing="prefix", block_size=BLOCK,
        )
        states = _drive_staggered(fleet, prompts)
        s = fleet.stats()
        assert s.prefix_hits > 0 and s.grafted_tokens > 0
        for st in states:
            assert tuple(st.generated) == solo[("packed", st.request.rid)]

    def test_prefix_saves_prefill_tokens(self, compiled, model):
        """The routing policies differ ONLY in work placement: prefix
        must strictly out-hit and out-save round-robin on the
        shared-prefix mix."""
        _, _, prompts = model
        cm = compiled["reference"]
        by_policy = {}
        for policy in ("prefix", "round-robin"):
            fleet = FleetEngine(
                [Replica(r, cm, max_batch=2, max_len=MAX_LEN)
                 for r in range(2)],
                routing=policy, block_size=BLOCK,
            )
            _drive_staggered(fleet, prompts)
            by_policy[policy] = fleet.stats()
        pfx, rr = by_policy["prefix"], by_policy["round-robin"]
        assert pfx.prefix_hits > 0 and rr.prefix_hits == 0
        assert pfx.prefix_hit_rate > rr.prefix_hit_rate
        assert pfx.prefill_tokens < rr.prefill_tokens
        assert pfx.grafted_tokens > 0 and rr.grafted_tokens == 0

    def test_stream_through_fleet(self, compiled, model, solo):
        _, _, prompts = model
        cm = compiled["reference"]
        fleet = FleetEngine(
            [Replica(r, cm, max_batch=2, max_len=MAX_LEN) for r in range(2)],
            routing="prefix", block_size=BLOCK,
        )
        got = list(fleet.stream(
            Request(rid=0, prompt=prompts[0], max_new_tokens=GEN)
        ))
        assert tuple(got) == solo[("reference", 0)]

    def test_duplicate_replica_ids_rejected(self, compiled):
        cm = compiled["reference"]
        with pytest.raises(RoutingConfigError, match="duplicate"):
            FleetEngine([
                Replica(0, cm, max_batch=1, max_len=MAX_LEN),
                Replica(0, cm, max_batch=1, max_len=MAX_LEN),
            ])


# ---------------------------------------------------------------------------
# failover off a degraded replica
# ---------------------------------------------------------------------------


class TestFailover:
    def test_degrade_fails_over_with_zero_failed(self, model):
        """Replica 0 (fault-injected, zero spares) loses a tile
        mid-serve -> degrades; every in-flight request must finish on
        replica 1 with solo-exact tokens and zero fleet-wide FAILED."""
        from repro.faults import FaultModel

        cfg, params, prompts = model
        gen = 16
        max_len = max(len(p) for p in prompts) + gen + 2
        clean = compiler_lib.HardwareTarget(
            engine="tiled", mapping_policy="tacitmap", spare_tiles=0
        )
        cm_ref = compiler_lib.compile(cfg, params, clean)
        refs = {}
        for i, p in enumerate(prompts[:4]):
            se = cm_ref.serve(max_batch=1, max_len=max_len)
            st = se.submit(Request(rid=i, prompt=p, max_new_tokens=gen))
            se.drain()
            refs[i] = tuple(st.generated)

        cm0 = compiler_lib.compile(
            cfg, params, dataclasses.replace(clean, fault_model=FaultModel())
        )
        r0 = Replica(0, cm0, max_batch=4, max_len=max_len)
        r1 = Replica(1, cm_ref, max_batch=4, max_len=max_len)
        fleet = FleetEngine([r0, r1], routing="least-loaded")
        states = [
            fleet.submit(Request(rid=i, prompt=p, max_new_tokens=gen))
            for i, p in enumerate(prompts[:4])
        ]
        victim = sorted({
            t for pw in cm0._fault_artifacts()
            for *_, t in cm0.engine._placement_blocks(pw.m, pw.n)
        })[0]
        ticks = 0
        while not fleet.idle() and ticks < 300:
            if ticks == 2:
                cm0.engine.fail_tile(victim)
                cm0.refresh_faults()
                r0.serving._rebind()
            fleet.step()
            ticks += 1

        assert r0.degraded_reason is not None and r1.healthy
        s = fleet.stats()
        assert s.failed == 0 and s.failovers > 0
        assert s.healthy_replicas == 1
        for st in states:
            assert st.status is RequestStatus.FINISHED
            assert tuple(st.generated) == refs[st.request.rid]
        moved = [st for st in states if st.failovers > 0]
        assert moved and all(st.replica == 1 for st in moved)

    def test_all_replicas_degraded_rejects(self, compiled, model):
        """With no healthy replica left, a new submission is REJECTED
        with the named degraded reason — same surface as a solo engine."""
        _, _, prompts = model
        cm = compiled["reference"]
        fleet = FleetEngine(
            [Replica(0, cm, max_batch=1, max_len=MAX_LEN)],
            routing="least-loaded",
        )
        fleet.replicas[0].scheduler.degrade("synthetic wipeout")
        st = fleet.submit(Request(rid=0, prompt=prompts[0],
                                  max_new_tokens=2))
        assert st.status is RequestStatus.REJECTED
        assert "wipeout" in st.reject_reason


# ---------------------------------------------------------------------------
# cross-pool snapshot portability (the failover salvage primitive)
# ---------------------------------------------------------------------------


class TestSnapshotPortability:
    def test_snapshot_restores_into_sibling_engine(self, model, solo):
        """A SlotSnapshot taken on one ServingEngine restores bit-exactly
        into a DIFFERENT engine compiled separately from the same
        HardwareTarget: prefill rows are prompt-length-invariant and the
        cache layout is target-determined, so KV rows are portable
        across pools — which is what fleet failover salvage relies on."""
        from repro.serving.scheduler import RequestState

        cfg, params, prompts = model
        target = compiler_lib.HardwareTarget(engine="reference")
        cm_a = compiler_lib.compile(cfg, params, target)
        cm_b = compiler_lib.compile(cfg, params, target)

        prompt = prompts[0]
        req = Request(rid=0, prompt=prompt, max_new_tokens=GEN)
        se_a = cm_a.serve(max_batch=1, max_len=MAX_LEN)
        slot = se_a.acquire_slot()
        st = RequestState(request=req, seq=0, submit_tick=0)
        se_a.prefill_into(slot, st)
        se_a.decode_tick({slot: st})
        snap = se_a.evict_slot(slot)
        assert isinstance(snap, SlotSnapshot)
        carried = list(st.generated)

        se_b = cm_b.serve(max_batch=1, max_len=MAX_LEN)
        slot_b = se_b.acquire_slot()
        se_b.restore_slot(slot_b, snap)
        st_b = RequestState(request=req, seq=0, submit_tick=0)
        st_b.generated = carried
        while len(st_b.generated) < GEN:
            se_b.decode_tick({slot_b: st_b})
        assert tuple(st_b.generated) == solo[("reference", 0)]

    def test_adopt_carries_tokens_and_snapshot(self, compiled, model, solo):
        """RequestScheduler.adopt: the fleet's failover admission —
        carried tokens don't re-fire, the snapshot resumes at admission,
        and the finished request matches its solo reference."""
        from repro.serving.scheduler import RequestState

        _, _, prompts = model
        cm = compiled["reference"]
        prompt = prompts[0]

        # interrupt a solo run mid-decode via the engine surface
        se_a = cm.serve(max_batch=1, max_len=MAX_LEN)
        slot = se_a.acquire_slot()
        st_a = RequestState(
            request=Request(rid=0, prompt=prompt, max_new_tokens=GEN),
            seq=0, submit_tick=0,
        )
        se_a.prefill_into(slot, st_a)
        se_a.decode_tick({slot: st_a})
        snap = se_a.evict_slot(slot)

        seen = []
        se_b = cm.serve(max_batch=2, max_len=MAX_LEN)
        st_b = se_b.scheduler.adopt(
            Request(rid=0, prompt=prompt, max_new_tokens=GEN,
                    on_token=lambda r, t, i: seen.append(t)),
            generated=list(st_a.generated),
            snapshot=snap,
        )
        assert st_b.status is RequestStatus.WAITING
        assert st_b.snapshot is snap
        se_b.drain()
        ref = solo[("reference", 0)]
        assert tuple(st_b.generated) == ref
        # only the resumed tokens fired the callback, not the carried ones
        assert tuple(seen) == ref[len(st_a.generated):]
        assert se_b.stats().scheduler.resumed == 1


# ---------------------------------------------------------------------------
# satellites: latency ledger, fleet pricing, section CLI
# ---------------------------------------------------------------------------


class TestLatencyLedger:
    def test_finish_tick_and_latency_recorded(self, compiled, model):
        _, _, prompts = model
        cm = compiled["reference"]
        se = cm.serve(max_batch=2, max_len=MAX_LEN)
        st = se.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=GEN))
        assert st.latency_ticks is None
        se.drain()
        assert st.finish_tick is not None
        assert st.latency_ticks == st.finish_tick - st.submit_tick
        assert st.latency_ticks > 0
        assert se.stats().scheduler.request_latency_ticks == pytest.approx(
            st.latency_ticks
        )

    def test_latency_histogram_exported(self, compiled, model):
        from repro import obs

        _, _, prompts = model
        cm = compiled["reference"]
        tel = obs.start()
        try:
            se = cm.serve(max_batch=1, max_len=MAX_LEN)
            se.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=GEN))
            se.drain()
            text = tel.metrics.render()
        finally:
            obs.stop()
        assert "repro_request_latency_ticks" in text


class TestFleetPrice:
    def test_linear_area_flat_wall_clock(self, compiled):
        from repro.core import costmodel

        base = compiled["reference"].price(n_active=2)
        fp = costmodel.fleet_price(base, 3, n_active=2)
        assert fp.tiles_total == 3 * base.n_tiles
        assert fp.programming_uj == pytest.approx(3 * base.programming_uj)
        assert fp.programming_us == base.programming_us
        assert fp.tick_latency_ns == base.tick_latency_ns
        assert fp.fleet_tokens_per_s == pytest.approx(
            3 * 2 / (base.tick_latency_ns * 1e-9)
        )
        assert fp.break_even_ticks == base.break_even_ticks
        assert "3 x" in fp.summary()

    def test_engine_price_matches_costmodel(self, compiled, model):
        from repro.core import costmodel

        cm = compiled["reference"]
        fleet = FleetEngine(
            [Replica(r, cm, max_batch=2, max_len=MAX_LEN) for r in range(2)]
        )
        fp = fleet.price(n_active=2)
        ref = costmodel.fleet_price(cm.price(n_active=2), 2, n_active=2)
        assert fp.tiles_total == ref.tiles_total
        assert fp.fleet_tokens_per_s == pytest.approx(ref.fleet_tokens_per_s)

    def test_rejects_zero_replicas(self, compiled):
        from repro.core import costmodel

        with pytest.raises(ValueError, match="n_replicas"):
            costmodel.fleet_price(compiled["reference"].price(), 0)


class TestSectionCLI:
    def test_list_sections(self, capsys):
        from benchmarks import run as bench_run

        assert bench_run.main(["--list-sections"]) == 0
        out = capsys.readouterr().out
        for section in ("fleet", "faults", "scheduler", "dse"):
            assert section in out.split() or section in out

    def test_unknown_section_names_the_menu(self, capsys):
        from benchmarks import run as bench_run

        with pytest.raises(SystemExit):
            bench_run.main(["--sections", "flet"])
        err = capsys.readouterr().err
        assert "unknown sections: flet" in err
        # the error must carry the menu, not send the user hunting
        assert "fleet" in err and "scheduler" in err and "engines" in err
