"""Scheduler-fronted continuous-batching serving engine.

The paper's accelerator streams independent inference requests through
resident weights (WDM multiplexes K of them onto one crossbar pass);
the LM serving analogue is continuous batching behind admission
control. The serving stack is two layers:

* :class:`ServingEngine` (this module) is the **slot pool executor**: a
  fixed pool of KV-cache slots over a
  :class:`repro.compiler.CompiledModel`, with K-group batched decode.
  It owns the caches, the jitted prefill/decode dispatches, and the
  per-slot state — and exposes the small pool surface the scheduler
  drives (``acquire_slot`` / ``prefill_into`` / ``decode_tick`` /
  ``evict_slot`` / ``restore_slot`` / ``release_slot``).
* :class:`repro.serving.scheduler.RequestScheduler` is the **request
  path** in front of it: waiting/running queues, FIFO + deadline
  policies, a KV-token budget with a reserve ratio, per-request SLOs
  (priority, ``deadline_ticks``) with graceful rejection, preemption
  back to waiting, and streaming token callbacks. Every client call on
  the engine — ``submit`` / ``step`` / ``drain`` / ``stream`` —
  delegates to its scheduler; ``run_to_completion`` survives as a thin
  wrapper over ``drain``.

The documented loop::

    compiled = repro.compiler.compile(cfg, params, target)
    se = compiled.serve(max_batch=8, max_len=256,
                        scheduler=SchedulerConfig(policy="deadline"))
    states = [se.submit(Request(rid=i, prompt=p, max_new_tokens=32))
              for i, p in enumerate(prompts)]
    se.drain()                      # or: se.step() per tick
    print(se.stats())               # one frozen ServingStats snapshot

Executor design (unchanged across the scheduler redesign):

* **Slot cache**: caches allocated once at (max_batch, max_len);
  requests claim a free slot, prefill writes their prompt KV into it,
  decode advances the active slots with per-slot positions
  (``attention_decode_step`` takes a (B,) position vector), finished
  slots are freed and immediately reusable — no cache reallocation,
  fixed memory.
* **K-group batching** (:class:`BatchPlanner`): every tick the planner
  collects the active slots into groups of up to K and the engine runs
  one decode over them. Inside the model, the binarized
  projections execute through a :class:`~repro.core.engine.GroupedEngine`
  — the whole tick's stacked activations go down as ONE
  ``binary_mmm(groups, w)`` call instead of one ``binary_vmm`` per
  slot. K is capability-aware (mapping plan's WDM capacity > engine
  capability > one vmap'd group); ragged tails pad the last group by
  repeating a real slot (an idle comb line), pad lanes discarded.
* **Crossbar programming** happened in ``compile()`` (PR 4/5): every
  binarized projection is resident in the backend's prepared form, so
  decode ticks trace zero weight-side transforms — the paper's
  Computation-In-Memory premise. Counted in ``stats().programmed`` /
  ``.program_s``.
* **Per-slot KV-cache scatter**: when the plan's lanes cover the pool
  (K of at least the pool, or enough live slots) the whole pool
  decodes in place (donated caches, zero copies): free slots ride
  along as discarded lanes at position 0 of their own rows, which the
  next prefill or restore into the slot overwrites. Only a plan with
  fewer lanes than slots (K below the pool, few live slots) gathers its
  lanes, decodes them and scatters their cache rows back, as ONE fused
  compiled dispatch per tick.
* **Preemption snapshots**: evicting a slot copies its exact cache
  rows + position + last token out of the pool; restoring them into
  any free slot resumes greedy decode bit-identically — the mechanism
  behind the scheduler's budget/priority preemption.
* **Greedy decoding** (argmax) — sampling is orthogonal to the engine.

The invariant, tested in tests/test_serving.py /
tests/test_serving_groups.py / tests/test_scheduler.py: any
interleaving of submissions, any group size, any execution backend,
any scheduling policy and any preemption pattern produce byte-identical
generations to running each request alone — batching and scheduling
are semantically invisible.

The legacy multi-knob constructor
``ServingEngine(cfg, params, engine=..., group_size=...)`` (deprecated
in PR 5) is REMOVED: the only construction is from a
:class:`~repro.compiler.CompiledModel`, and old call sites get a
:class:`LegacyServingSignatureError` naming ``repro.compiler.compile``.

This engine is CPU/TPU-agnostic pure JAX over the model zoo's
prefill/decode entry points (decoder-only archs incl. SSM/hybrid).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import lm as lm_lib
from repro.models.layers import EXACT_COMPILER_OPTIONS
from repro.serving.scheduler import (
    Request,
    RequestScheduler,
    RequestState,
    SchedulerConfig,
    SchedulerStats,
    SlotSnapshot,
)

Array = jax.Array


def _single_device(tree: Any):
    """The one device holding every array in ``tree``, else ``None``
    (a sharded or empty pytree: no placement is imposed)."""
    devices = {
        d for x in jax.tree.leaves(tree) if isinstance(x, jax.Array)
        for d in x.devices()
    }
    return devices.pop() if len(devices) == 1 else None


class LegacyServingSignatureError(TypeError):
    """The pre-compiler ``ServingEngine(cfg, params, engine=...)``
    signature was removed in PR 7; compile a target instead."""


@dataclasses.dataclass(frozen=True)
class ServingStats:
    """One frozen snapshot of the serving engine's counters.

    Replaces the PR 3-6 ad-hoc ``stats`` dict + ``cache_stats()`` pair:
    executor counters here, the request path nested as ``scheduler``,
    and the bound backend's cache hit/miss counters as ``caches``.
    """

    ticks: int                  # decode launches
    gathered_ticks: int         # ... of them through the gather/scatter path
    masked_ticks: int           # ... of them over a partly live pool in place
    decoded: int                # real slot-tokens decoded
    mmm_groups: int             # K-groups issued to a registry backend
    pad_lanes: int              # idle wavelengths from ragged tails
    prefills: int
    prefill_tokens: int         # prompt tokens actually prefilled
    grafted_tokens: int         # prompt tokens elided by prefix grafts
    evictions: int              # preemption snapshots taken
    restores: int               # snapshots grafted back into a slot
    programmed: int             # projections made resident in compile()
    program_s: float            # one-time programming wall time
    scheduler: SchedulerStats
    caches: dict[str, dict[str, int]]   # backend cache counters ({} on plain jnp)


@dataclasses.dataclass(frozen=True)
class GroupPlan:
    """One tick's unit of work: active slots arranged into K-groups."""

    slots: tuple[int, ...]        # real active slots, in slot order
    k: int                        # group size (wavelengths per group)

    @property
    def n_active(self) -> int:
        return len(self.slots)

    @property
    def n_groups(self) -> int:
        """Crossbar MMM activations this tick costs per projection —
        the decode tick count in hardware-step terms (ceil(active/K))."""
        return math.ceil(self.n_active / self.k)

    @property
    def n_lanes(self) -> int:
        return self.n_groups * self.k

    @property
    def n_pad(self) -> int:
        """Idle wavelengths: lanes in the ragged tail carrying no slot."""
        return self.n_lanes - self.n_active

    def gather_indices(self) -> np.ndarray:
        """(n_lanes,) slot indices for the gathered decode batch; the
        ragged tail repeats the last real slot (outputs discarded)."""
        idx = np.empty((self.n_lanes,), np.int32)
        idx[: self.n_active] = self.slots
        idx[self.n_active:] = self.slots[-1]
        return idx


class BatchPlanner:
    """Collects active slots into WDM-style K-groups each tick.

    The contract (documented in ROADMAP.md §Serving batching): given
    the set of active slots, produce a :class:`GroupPlan` whose lanes
    are a static multiple of K — ceil(active/K) groups, ragged tail
    padded — or ``None`` when nothing is active. The serving engine
    issues one decode dispatch per plan; a future multi-device serving
    path shards *groups* (not slots) across devices from the same plan.
    """

    def __init__(self, k: int):
        if k < 1:
            raise ValueError(f"group size must be >= 1, got {k}")
        self.k = int(k)

    def plan(self, active_slots: list[int]) -> GroupPlan | None:
        if not active_slots:
            return None
        return GroupPlan(slots=tuple(sorted(active_slots)), k=self.k)


class ServingEngine:
    """Slot-pool executor + scheduler front over a
    :class:`repro.compiler.CompiledModel`.

    Construction is ``compile(cfg, params, target).serve()`` (or
    equivalently ``ServingEngine(compiled_model)``): the compiler
    pipeline has already mapped, validated and programmed the target,
    so serving just binds the slot pool and its request scheduler.
    """

    def __init__(
        self,
        compiled,
        *legacy_args,
        max_batch: int = 4,
        max_len: int = 256,
        scheduler: SchedulerConfig | None = None,
        **legacy_kwargs,
    ):
        from repro import compiler as compiler_lib

        if legacy_args or legacy_kwargs or not isinstance(
            compiled, compiler_lib.CompiledModel
        ):
            bad = sorted(legacy_kwargs) or ["positional params"]
            raise LegacyServingSignatureError(
                "the legacy ServingEngine(cfg, params, engine=/group_size=/"
                "mapping_plan=/prepare_weights=) signature was removed "
                f"(got: {', '.join(bad)}); build a repro.compiler."
                "HardwareTarget and pass repro.compiler.compile(cfg, "
                "params, target) — or call its .serve(max_batch=..., "
                "max_len=..., scheduler=SchedulerConfig(...))"
            )
        self.compiled = compiled
        cfg = compiled.cfg
        self.cfg = cfg
        self.params = compiled.params
        self.max_batch = max_batch
        self.max_len = max_len
        self.mapping_plan = compiled.plan

        # K-group sizing: explicit target K > mapping plan's WDM
        # capacity > engine capability > one vmap'd group (one policy
        # for every consumer: engine_lib.resolve_group_size, applied by
        # the compiled model)
        self.group_k = compiled.group_size_for(max_batch)
        self.planner = BatchPlanner(self.group_k)
        self._exec = compiled.executor(max_batch)
        self.engine_name = compiled.target.engine
        self._counts = {
            "ticks": 0, "gathered_ticks": 0, "masked_ticks": 0, "decoded": 0,
            "mmm_groups": 0, "pad_lanes": 0, "prefills": 0, "prefill_tokens": 0,
            "grafted_tokens": 0, "evictions": 0, "restores": 0,
        }

        # prefix grafting (PR 10): a continuation prefill slices cached
        # KV at a token boundary, which only attention mixers support
        # (SSM/hybrid state is recurrent) and only the token-prompt path
        # can hash (VLM prompts prepend frontend embeddings)
        self.supports_prefix_graft = (
            all(kind.mixer == "attn" for kind in cfg.pattern)
            and cfg.frontend != "vision"
        )
        # fleet hooks (PR 10): `prefill_observer(state, prompt_rows)` is
        # called after every prefill with the prompt's batch-squeezed
        # cache rows (the router's prefix-library feed); `on_degrade`
        # fires when the health monitor degrades this engine
        self.prefill_observer = None
        self.on_degrade = None

        # the pool lives where the programmed params live: a fleet
        # replica placed on its own chip keeps its KV there too
        self.device = _single_device(self.params)
        with jax.default_device(self.device):
            self.caches = self._to_device(lm_lib.init_cache(cfg, max_batch, max_len))
        self.pos = np.zeros((max_batch,), np.int32)        # next write position
        self.tok = np.zeros((max_batch,), np.int32)        # last emitted token
        self._free = set(range(max_batch))

        self._build_dispatches()

        self.scheduler = RequestScheduler(self, scheduler)

        # fault tolerance (PR 9): a fault-injecting backend gets a
        # health monitor — sampled consistency sweeps, quarantine +
        # remap, K shrink on dead lanes, graceful degradation
        from repro.faults.engine import FaultyEngine
        from repro.faults.monitor import HealthMonitor

        self.health = (
            HealthMonitor(self)
            if isinstance(compiled.engine, FaultyEngine) else None
        )

    def _build_dispatches(self) -> None:
        """(Re)build the jitted prefill/decode dispatches around the
        CURRENT executor. Called at construction and by :meth:`_rebind`
        after a fault remap — the closures capture the executor by
        value, so stale jit caches can never serve a replaced engine."""
        cfg = self.cfg
        ex = self._exec
        jit = functools.partial(jax.jit, compiler_options=EXACT_COMPILER_OPTIONS)

        # every dispatch runs under a root named scope (`prefill` or
        # `decode`), so each device op's HLO op_name says which program
        # part it belongs to; scopes set metadata only, not arithmetic
        @jit
        def prefill(p, t):
            with jax.named_scope("prefill"):
                return lm_lib.prefill(p, t, cfg, engine=ex)

        # prefix-graft continuation: suffix tokens over donated prefix
        # rows, returning full-prompt-shaped caches (specializes on the
        # (prefix_len, suffix_len) pair — block-aligned grafts keep the
        # shape set small)
        @jit
        def prefill_cont(p, t, pre):
            with jax.named_scope("prefill"):
                return lm_lib.prefill_continue(p, t, pre, cfg, engine=ex)

        self._prefill = prefill
        self._prefill_cont = prefill_cont

        def gathered_decode(p, tok, pos, caches, idx):
            # gather -> decode -> per-slot scatter, fused into ONE
            # compiled dispatch per tick (specializes on the lane count:
            # at most ceil(max_batch/K) distinct shapes, reused
            # steady-state). Pad lanes mirror a real slot and therefore
            # compute bit-identical updates, so scattering every lane is
            # exact; slots outside `idx` are never touched.
            with jax.named_scope("decode"):
                with jax.named_scope("pool.gather"):
                    gathered = jax.tree.map(lambda c: jnp.take(c, idx, axis=1), caches)
                    tok, pos = tok[idx], pos[idx]
                logits, new_c = lm_lib.decode_step(p, tok, pos, gathered, cfg, engine=ex)
                with jax.named_scope("pool.scatter"):
                    caches = jax.tree.map(
                        lambda dst, src: dst.at[:, idx].set(src.astype(dst.dtype)),
                        caches,
                        new_c,
                    )
            return logits, caches

        def full_decode(p, tok, pos, caches):
            with jax.named_scope("decode"):
                return lm_lib.decode_step(p, tok, pos, caches, cfg, engine=ex)

        # the cache pytree (argnum 3 in both decode entry points) is
        # DONATED: tick N's caches update in place instead of being
        # copied. `decode_tick()` immediately rebinds `self.caches` to
        # the returned pytree, so the consumed input is never reused.
        self._decode = jit(gathered_decode, donate_argnums=(3,))
        # in-place path: whenever the plan's lanes cover the pool the
        # gather would compute no fewer lanes than the pool itself, so
        # decode every slot in place and skip the two O(pool * max_len)
        # cache copies; free slots (pos 0, tok 0) are discarded lanes
        self._decode_full = jit(full_decode, donate_argnums=(3,))

    def _rebind(self) -> None:
        """Resynchronize with the compiled model after it changed under
        us (fault remap re-placed the plan / dead lanes shrank K):
        refreshed params, a new K planner, and freshly traced
        dispatches over the new executor."""
        self.params = self.compiled.params
        self.group_k = self.compiled.group_size_for(self.max_batch)
        self.planner = BatchPlanner(self.group_k)
        self._exec = self.compiled.executor(self.max_batch)
        self._build_dispatches()

    # -- client API (delegates to the request scheduler) ---------------------

    def submit(self, request: Request) -> RequestState:
        """Enqueue a request; returns its (possibly REJECTED) state."""
        return self.scheduler.submit(request)

    def step(self) -> list[RequestState]:
        """One scheduling tick: expire/admit/preempt, then one K-grouped
        decode over the active slots; returns newly terminal states."""
        return self.scheduler.step()

    def drain(self, max_ticks: int = 10_000) -> list[RequestState]:
        """Step until idle; raises ``SchedulerExhaustedError`` (with
        queue-depth and budget context) on tick exhaustion."""
        return self.scheduler.drain(max_ticks)

    def run_to_completion(self, max_ticks: int = 10_000) -> list[RequestState]:
        """Thin wrapper over :meth:`drain` (the historical name)."""
        return self.drain(max_ticks)

    def stream(self, request: Request):
        """Submit and iterate the request's tokens as they decode."""
        return self.scheduler.stream(request)

    def idle(self) -> bool:
        return self.scheduler.idle()

    def stats(self) -> ServingStats:
        """One frozen snapshot: executor counters + nested scheduler
        stats + the bound backend's cache hit/miss counters."""
        backend = (
            self._exec.cache_stats()
            if self._exec is not None and hasattr(self._exec, "cache_stats")
            else {}
        )
        return ServingStats(
            **self._counts,
            programmed=self.compiled.programmed,
            program_s=self.compiled.program_s,
            scheduler=self.scheduler.stats(),
            caches=backend,
        )

    # -- slot-pool surface (driven by RequestScheduler) ----------------------

    @property
    def n_slots(self) -> int:
        return self.max_batch

    @property
    def slot_capacity(self) -> int:
        """KV rows one slot holds — the scheduler's budget unit."""
        return self.max_len

    @property
    def free_slots(self) -> int:
        return len(self._free)

    def acquire_slot(self) -> int:
        if not self._free:
            raise RuntimeError("no free slot (scheduler admitted past the pool)")
        slot = min(self._free)
        self._free.remove(slot)
        return slot

    def release_slot(self, slot: int) -> None:
        self.pos[slot] = 0
        self.tok[slot] = 0
        self._free.add(slot)

    def prefill_into(self, slot: int, st: RequestState) -> None:
        """Run the request's prompt prefill and graft its KV into the
        slot; emits the first (argmax) token onto the state.

        A request carrying a :class:`~repro.serving.scheduler
        .PrefixGraft` (fleet prefix-affinity hit) skips recomputing the
        shared prefix: the donated rows stand in for positions
        ``[0, length)`` and only the suffix runs, through
        ``prefill_continue`` — bit-identical to the full prefill."""
        plen = st.request.prompt_len
        prompt = jnp.asarray(st.request.prompt, jnp.int32)[None, :]
        graft = st.request.prefix
        use_graft = (
            graft is not None and self.supports_prefix_graft
            and 0 < graft.length < plen
        )
        with obs.span(
            "prefill", track="serve", engine=self.engine_name,
            slot=slot, rid=st.request.rid, prompt_len=plen,
            grafted=graft.length if use_graft else 0,
        ) as sp:
            with obs.span("prefill.run", track="serve"):
                if use_graft:
                    # library rows may come from another replica's device
                    pre_rows = jax.tree.map(
                        lambda r: r[:, None, : graft.length],
                        self._to_device(graft.rows),
                    )
                    logits, pre = self._prefill_cont(
                        self.params, prompt[:, graft.length:], pre_rows
                    )
                    self._counts["grafted_tokens"] += graft.length
                    self._counts["prefill_tokens"] += plen - graft.length
                else:
                    logits, pre = self._prefill(self.params, prompt)
                    self._counts["prefill_tokens"] += plen
            with obs.span("prefill.graft", track="serve"):
                self._graft(slot, pre, plen)
            with obs.span("prefill.read", track="serve"):
                st.emit(int(jnp.argmax(logits[0])))
            sp.fence(self.caches)
        self.pos[slot] = plen
        self.tok[slot] = st.generated[-1]
        self._counts["prefills"] += 1
        if self.prefill_observer is not None:
            # full-prompt-shaped rows either way (continuation returns
            # prefix + suffix concatenated) — the fleet prefix library
            # extends its chains from grafted admissions too
            self.prefill_observer(st, jax.tree.map(lambda c: c[:, 0], pre))
        if obs.enabled():
            obs.observe(
                "repro_prefill_latency_seconds", sp.duration_s,
                "prompt prefill wall time (graft fenced)",
                engine=self.engine_name,
            )
            obs.count(
                "repro_prefills_total", 1, "prompt prefills run",
                engine=self.engine_name,
            )

    def slot_exhausted(self, slot: int) -> bool:
        """True when the next decode write would run off the slot."""
        return self.pos[slot] + 1 >= self.max_len

    def evict_slot(self, slot: int) -> SlotSnapshot:
        """Copy the slot's exact execution state out of the pool (the
        rows are materialized as NEW arrays, so later donated decode
        ticks cannot alias them) and free the slot."""
        rows = jax.tree.map(lambda c: jnp.array(c[:, slot]), self.caches)
        snap = SlotSnapshot(
            pos=int(self.pos[slot]), tok=int(self.tok[slot]), rows=rows,
            tick=self._counts["ticks"],
        )
        self.release_slot(slot)
        self._counts["evictions"] += 1
        return snap

    def restore_slot(self, slot: int, snap: SlotSnapshot) -> None:
        """Graft a preemption snapshot into a (possibly different) free
        slot. The full row is restored — including the stale region
        beyond ``pos``, which attention masks exactly as it does for a
        reused slot — so resumed decode is bit-identical."""
        self.caches = jax.tree.map(
            lambda dst, src: dst.at[:, slot].set(src.astype(dst.dtype)),
            self.caches,
            self._to_device(snap.rows),  # failover: from another replica
        )
        self.pos[slot] = snap.pos
        self.tok[slot] = snap.tok
        self._counts["restores"] += 1

    def decode_tick(self, running: dict[int, RequestState]) -> None:
        """One K-grouped decode over the running slots: plan, one
        dispatch (the pool in place, or gather/decode/scatter when the
        plan's lanes are fewer than the slots), then emit each slot's
        token.

        With telemetry on (:mod:`repro.obs`), each tick records a fenced
        ``decode_tick`` span (engine, K, active/group/pad lanes, cache
        hit/miss deltas) plus tick-latency histogram and lane counters;
        with telemetry off the tick pays one ``None`` check and no extra
        host synchronization.
        """
        plan = self.planner.plan(list(running))
        if plan is None:
            return
        if not obs.enabled():
            self._run_tick(plan, running)
            if self.health is not None:
                self.health.after_tick()
            return
        before = self._cache_totals()
        with obs.span(
            "decode_tick", track="serve", engine=self.engine_name,
            k=plan.k, n_active=plan.n_active, n_groups=plan.n_groups,
            n_pad=plan.n_pad, path=self._path(plan),
        ) as sp:
            self._run_tick(plan, running)
            sp.fence(self.caches)
            after = self._cache_totals()
            sp.set(
                cache_hits=after[0] - before[0],
                cache_misses=after[1] - before[1],
            )
        obs.observe(
            "repro_tick_latency_seconds", sp.duration_s,
            "K-grouped decode tick wall time (caches fenced)",
            engine=self.engine_name, k=plan.k,
        )
        obs.count(
            "repro_decode_ticks_total", 1, "decode launches",
            engine=self.engine_name,
        )
        obs.count(
            "repro_decoded_tokens_total", plan.n_active,
            "real slot-tokens decoded", engine=self.engine_name,
        )
        if self._exec is not None:
            obs.count(
                "repro_mmm_groups_total", plan.n_groups,
                "K-groups issued to a registry backend",
                engine=self.engine_name,
            )
        if plan.n_pad:
            obs.count(
                "repro_pad_lanes_total", plan.n_pad,
                "idle wavelengths from ragged tails",
                engine=self.engine_name,
            )
        if self.health is not None:
            self.health.after_tick()

    def _cache_totals(self) -> tuple[int, int]:
        """(hits, misses) summed over the backend's caches — the span's
        per-tick delta source (only read with telemetry on)."""
        if self._exec is None or not hasattr(self._exec, "cache_stats"):
            return (0, 0)
        stats = self._exec.cache_stats().values()
        return (
            sum(s.get("hits", 0) for s in stats),
            sum(s.get("misses", 0) for s in stats),
        )

    def _path(self, plan: GroupPlan) -> str:
        """The tick's dispatch. When the plan's lanes cover the pool a
        gather would compute no fewer lanes than the pool and copy it
        out and back besides, so the pool decodes in place: ``"full"``
        with every slot live, ``"masked"`` with some free. A free slot
        holds pos 0 and tok 0, so its discarded lane writes position 0
        of its own row (or its own SSM state row), which the next
        ``_graft`` or ``restore_slot`` into the slot overwrites. Fewer
        lanes than slots: ``"gathered"``."""
        if plan.n_lanes < self.max_batch:
            return "gathered"
        return "full" if plan.n_active == self.max_batch else "masked"

    def _run_tick(self, plan: GroupPlan, running: dict[int, RequestState]) -> None:
        """The tick body: one dispatch, counters, token emission."""
        path = self._path(plan)
        with obs.span("decode.dispatch", track="serve"):
            if path == "gathered":
                logits, self.caches = self._decode(
                    self.params,
                    jnp.asarray(self.tok),
                    jnp.asarray(self.pos),
                    self.caches,
                    jnp.asarray(plan.gather_indices()),
                )
            else:
                logits, self.caches = self._decode_full(
                    self.params, jnp.asarray(self.tok), jnp.asarray(self.pos), self.caches
                )
        n = plan.n_active
        self._counts["ticks"] += 1
        if path == "gathered":
            self._counts["gathered_ticks"] += 1
        elif path == "masked":
            self._counts["masked_ticks"] += 1
        self._counts["decoded"] += n
        # K-groups actually issued to a registry backend; the plain-jnp
        # path (no engine) executes no binary_mmm, so its reduction is
        # not reported as a measurement
        if self._exec is not None:
            self._counts["mmm_groups"] += plan.n_groups
        self._counts["pad_lanes"] += plan.n_pad

        # an in-place lane is its slot; a gathered lane is the slot's
        # place in the plan
        lanes = range(n) if path == "gathered" else plan.slots
        with obs.span("decode.read", track="serve"):
            nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        with obs.span("decode.emit", track="serve"):
            for lane, slot in zip(lanes, plan.slots):
                st = running[slot]
                st.emit(int(nxt[lane]))
                self.pos[slot] += 1
                self.tok[slot] = nxt[lane]

    # -- internals ------------------------------------------------------------

    def _to_device(self, tree: Any) -> Any:
        """Commit ``tree`` to this engine's device (no-op when unplaced)."""
        return tree if self.device is None else jax.device_put(tree, self.device)

    def _graft(self, slot: int, pre_caches: Any, prompt_len: int) -> None:
        """Write one request's prompt KV/states into its slot."""

        def one(dst, src):
            if dst.ndim == 5 and src.ndim == 5 and dst.shape[2] >= src.shape[2]:
                # attn KV (L, B, T, KV, D): batch row `slot`, first T rows
                return dst.at[:, slot, : src.shape[2]].set(src[:, 0].astype(dst.dtype))
            # SSM conv/state (L, B, ...): replace the whole row
            return dst.at[:, slot].set(src[:, 0].astype(dst.dtype))

        self.caches = jax.tree.map(one, self.caches, pre_caches)
