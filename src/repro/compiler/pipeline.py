"""``compile(cfg, params, target) -> CompiledModel`` — the one-call
hardware-compilation pipeline.

The paper's flow is a single pipeline: map the BNN onto the crossbar
(TacitMap), program the oPCM cells once, then stream activations under
WDM (EinsteinBarrier). ``compile`` runs exactly that, in the canonical
order, from one :class:`~repro.compiler.target.HardwareTarget`:

1. **Validate** the target eagerly (named :class:`TargetError`\\ s —
   plan+engine mismatch, spec mismatch, K over plan capacity).
2. **Map**: compile an explicit layer->tile
   :class:`~repro.mapping.allocator.MappingPlan`
   (``mapping.compile_plan``) when the target names a policy/budget, or
   bind a pre-compiled plan passed by the caller.
3. **Resolve** the execution backend from the registry
   (``engine_lib.get_engine``; ``tiled`` binds the plan) and flip the
   model config to ``quant="bnn"`` for non-reference engines — a
   hardware backend executes the binarized projections.
4. **Program**: run the one-time crossbar write
   (``lm.program_weights``) so every binarized projection is resident
   in the engine's prepared form and decode ticks stream only
   activations.

The returned :class:`CompiledModel` is the single artifact every
consumer drives: ``prefill()`` / ``decode_step()`` for batch serving
loops, ``serve()`` for a bound continuous-batching
:class:`~repro.serving.engine.ServingEngine`, ``price()`` for the cost
model's plan + programming + per-tick readout report, ``describe()``
for the placement/pricing tables.

One-call replacements for the old multi-knob recipes::

    # was: get_engine("wdm") + replace(cfg, quant="bnn", bnn_engine=..)
    #      + GroupedEngine(eng, k) + lm.program_weights(...) in 4 places
    cm = compile(cfg, params, HardwareTarget(engine="wdm", group_size=4))
    logits, caches = cm.prefill(tokens)
    logits, caches = cm.decode_step(tok, pos, caches)

    # was: compile_plan(cfg, policy=..) + get_engine("tiled", plan=..)
    #      + ServingEngine(cfg, params, engine="tiled", mapping_plan=..)
    cm = compile(cfg, params, HardwareTarget(engine="tiled",
                                             mapping_policy="greedy"))
    se = cm.serve(max_batch=8, max_len=256)

    # was: nothing — pricing required hand-wiring costmodel pieces
    print(cm.price().summary())
    print(cm.describe())
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

from repro import obs
from repro.compiler.target import (
    GroupSizeError,
    HardwareTarget,
    PlanEngineMismatchError,
    SpecMismatchError,
    TargetError,
)
from repro.core import engine as engine_lib
from repro.core.crossbar import CrossbarSpec, EPCM_TILE, OPCM_TILE


def _default_spec(engine_name: str) -> CrossbarSpec:
    """The tile catalogue entry an engine defaults to (its capability row)."""
    try:
        info = engine_lib.engine_info(engine_name)
    except Exception:
        return EPCM_TILE
    return OPCM_TILE if getattr(info, "default_spec", "ePCM") == "oPCM" else EPCM_TILE


def resolve_engine(target: HardwareTarget, cfg=None, plan=None):
    """Resolve a target's execution backend (``None`` = plain-jnp path).

    The ``tiled`` engine binds ``plan`` when given one, else places
    ad hoc under ``target.mapping_policy`` (falling back to the config's
    policy). Shared by :func:`compile` and by benchmark sweeps that need
    the raw engine without a model (e.g. the mapping parity sweep).
    """
    if target.engine in ("", "reference"):
        return None
    kw = {}
    if target.engine == "packed":
        # fused decode-tick kernel vs the unfused multi-op baseline
        kw = {"fused": target.fused}
    if target.engine == "tiled":
        # ad-hoc fallback placements (projection shapes absent from the
        # plan) must land under the SAME policy the plan/config reports:
        # explicit target policy > the bound plan's > the config's
        policy = target.mapping_policy
        if policy is None and plan is not None:
            policy = plan.policy
        if policy is None and cfg is not None:
            policy = getattr(cfg, "mapping_policy", None)
        kw = {"plan": plan, "policy": policy or "tacitmap"}
        if target.mesh_axis is not None:
            kw["mesh_axis"] = target.mesh_axis
    base = engine_lib.get_engine(target.engine, target.spec, **kw)
    if target.fault_model is not None:
        from repro.faults.engine import FaultyEngine

        base = FaultyEngine(base, target.fault_model)
    return base


def compile(cfg, params, target: HardwareTarget, *, plan=None) -> "CompiledModel":
    """Compile a model onto a hardware target: map -> program -> execute.

    ``cfg`` is a :class:`~repro.models.config.ModelConfig` (decoder-only
    LM stack); ``params`` its parameter pytree, or ``None`` for a
    price-only compilation (``price()``/``describe()`` work, execution
    entry points raise). ``plan`` optionally binds a pre-compiled
    :class:`~repro.mapping.allocator.MappingPlan` instead of compiling
    one from ``target.mapping_policy``.

    Validates the whole combination eagerly (:class:`TargetError`
    subclasses name the mismatch) and returns a :class:`CompiledModel`.

    When a telemetry session is active (:mod:`repro.obs`) each pipeline
    stage — validate / map / resolve / program — records a span on the
    ``compile`` track, with the one-time programming cost attached.
    """
    with obs.span("compile", track="compile",
                  engine=target.engine, model=getattr(cfg, "name", "?")) as root:
        cm = _compile_staged(cfg, params, target, plan)
        root.set(programmed=cm.programmed, program_s=cm.program_s)
        return cm


def _compile_staged(cfg, params, target, plan) -> "CompiledModel":
    with obs.span("compile.validate", track="compile"):
        target = target.validate()
        if getattr(cfg, "is_encdec", False) and target.engine != "reference":
            raise TargetError(
                f"{cfg.name}: hardware targets compile the decoder-only LM "
                "projection stack; enc-dec models serve through "
                "cfg.bnn_engine directly"
            )

    # -- map: the explicit layer->tile placement ---------------------------
    with obs.span("compile.map", track="compile") as map_span:
        plan = _map_stage(cfg, target, plan)
        if plan is not None:
            map_span.set(policy=plan.policy, n_tiles=plan.n_tiles)

    # -- resolve: registry backend + bnn config ----------------------------
    with obs.span("compile.resolve", track="compile") as res_span:
        base, cfg = _resolve_stage(cfg, target, plan)
        res_span.set(backend=base.name if base is not None else "none")

    # -- program: the one-time crossbar write ------------------------------
    from repro.models import lm as lm_lib

    if params is not None:
        # one eager alpha per projection, shared by every target built
        # from these params (the reference included)
        params = lm_lib.attach_weight_scales(params, cfg)
    programmed, program_s = 0, 0.0
    if params is not None and base is not None and target.prepare_weights:
        with obs.span("compile.program", track="compile") as prog_span:
            t0 = time.perf_counter()
            params, programmed = lm_lib.program_weights(params, cfg, base)
            prog_span.fence(params)
            program_s = time.perf_counter() - t0
            prog_span.set(programmed=programmed, program_s=program_s)

    return CompiledModel(
        cfg=cfg,
        params=params,
        target=target,
        plan=plan,
        engine=base,
        programmed=programmed,
        program_s=program_s,
    )


def _map_stage(cfg, target, plan):
    if plan is not None:
        if target.engine != "tiled":
            raise PlanEngineMismatchError(
                f"a MappingPlan was passed but the target's engine is "
                f"{target.engine!r} — only the plan-driven 'tiled' engine "
                "executes a placement (the old ServingEngine silently used "
                "such a plan for K only)"
            )
        if target.spec is not None and plan.spec != target.spec:
            raise SpecMismatchError(
                f"plan was compiled for {plan.spec.technology} "
                f"{plan.spec.rows}x{plan.spec.cols} tiles but the target "
                f"binds {target.spec.technology} "
                f"{target.spec.rows}x{target.spec.cols} — recompile the plan "
                "on the target's spec"
            )
        # a bound plan already fixed the allocator choices; a target
        # naming different ones would be a silent knob drop
        if (
            target.mapping_policy is not None
            and target.mapping_policy != plan.policy
        ):
            raise TargetError(
                f"target names mapping_policy={target.mapping_policy!r} but "
                f"binds a plan compiled under {plan.policy!r} — drop the "
                "field or recompile the plan under the target's policy"
            )
        if (
            target.tile_budget is not None
            and target.tile_budget != plan.tile_budget
        ):
            raise TargetError(
                f"target names tile_budget={target.tile_budget} but binds a "
                f"plan compiled with tile_budget={plan.tile_budget} — drop "
                "the field or recompile the plan under the target's budget"
            )
        if target.spare_tiles and len(plan.spares) != target.spare_tiles:
            raise TargetError(
                f"target names spare_tiles={target.spare_tiles} but binds a "
                f"plan provisioning {len(plan.spares)} spare(s) — drop the "
                "field or recompile the plan with the target's spare budget"
            )
    elif target.wants_plan:
        from repro.mapping import compile_plan

        plan = compile_plan(
            cfg,
            spec=target.spec or _default_spec(target.engine),
            policy=target.mapping_policy or cfg.mapping_policy or "tacitmap",
            tile_budget=target.tile_budget,
            spare_tiles=target.spare_tiles,
        )
    return plan


def _resolve_stage(cfg, target, plan):
    base = resolve_engine(target, cfg, plan)
    if base is not None:
        # a hardware backend executes the binarized projections, so it
        # implies quant="bnn" (same contract as the old per-consumer
        # wiring); for tiled, pin the policy so any ad-hoc fallback
        # placement matches the plan's policy
        upd: dict[str, Any] = {"quant": "bnn", "bnn_engine": target.engine}
        if target.engine == "tiled" and (target.mapping_policy or plan is not None):
            upd["mapping_policy"] = (
                target.mapping_policy if target.mapping_policy is not None
                else plan.policy
            )
        cfg = dataclasses.replace(cfg, **upd)

    # -- K-group capacity: reject widths the hardware cannot multiplex ----
    if target.group_size is not None:
        cap = None
        if plan is not None:
            cap, what = plan.preferred_group_size(), "the plan's placed tiles"
        elif base is not None and base.info.native_mmm:
            cap, what = base.preferred_group_size(), f"engine {base.name!r}"
        if cap is not None and target.group_size > cap:
            raise GroupSizeError(
                f"group_size={target.group_size} exceeds the WDM capacity "
                f"K={cap} of {what} — more K-groups cannot ride one "
                "crossbar step than the tile has wavelengths"
            )

    return base, cfg


@dataclasses.dataclass(frozen=True)
class RemapReport:
    """``CompiledModel.remap()``: what moved and what reprogramming cost.

    ``cost`` is a ``costmodel.ProgrammingCost`` covering ONLY the moved
    blocks — incremental remapping's point is that untouched tiles keep
    their cells."""

    moves: tuple          # mapping.BlockMove per relocated block
    cost: Any             # costmodel.ProgrammingCost of the reprogram
    failed_tiles: frozenset[int]
    spares_left: int


@dataclasses.dataclass(frozen=True)
class TargetPrice:
    """``CompiledModel.price()``: the cost model's three seams in one
    report — plan execution, one-time programming, per-tick readout."""

    target: HardwareTarget
    design: str           # CIM design the tile spec implies
    policy: str
    n_tiles: int          # physical tiles provisioned (the area axis)
    utilization: float
    k: int                # WDM capacity of the priced tiles
    binary_steps: int
    latency_s: float      # per inference (plan schedule + edge layers)
    energy_j: float
    programming_cells: int
    programming_uj: float  # one-time PCM write energy
    programming_us: float
    tick_latency_ns: float  # one K-grouped decode tick, all binary layers
    tick_energy_pj: float
    break_even_ticks: float  # ticks until the write has paid for itself
    plan_cost: Any        # the full costmodel.PlanCost (per-layer rows)

    def summary(self) -> str:
        return (
            f"[price] {self.plan_cost.model} on {self.design} "
            f"(policy={self.policy}, {self.n_tiles} tiles, K={self.k}): "
            f"{self.latency_s * 1e6:.2f} us/inf, {self.energy_j * 1e6:.3f} uJ/inf; "
            f"program {self.programming_uj:.2f} uJ / {self.programming_us:.1f} us "
            f"(break-even {self.break_even_ticks:.0f} ticks); "
            f"tick {self.tick_latency_ns * 1e-3:.2f} us / {self.tick_energy_pj:.1f} pJ"
        )


class CompiledModel:
    """The artifact ``compile()`` returns: model + target, executable.

    Holds the post-pipeline state — the bnn-flipped config, the
    programmed params, the compiled plan and the resolved backend — and
    exposes every way the stack is driven:

    * :meth:`prefill` / :meth:`decode_step` — jitted LM entry points
      with the target's K-grouped executor bound (batch loops,
      ``launch/serve.py``).
    * :meth:`serve` — a bound continuous-batching ``ServingEngine``.
    * :meth:`price` — plan + programming + per-tick readout in one
      :class:`TargetPrice` (works without params: DSE sweeps compile
      price-only models).
    * :meth:`describe` — placement + pricing tables via
      ``mapping.report``.
    """

    def __init__(self, *, cfg, params, target, plan, engine, programmed, program_s):
        self.cfg = cfg
        self.params = params
        self.target = target
        self.plan = plan
        self.engine = engine          # resolved base backend (None = plain jnp)
        self.programmed = programmed  # projection instances programmed
        self.program_s = program_s    # crossbar-programming wall time
        self._jit: dict[int, tuple] = {}
        self._price_plan = plan

    # -- execution ----------------------------------------------------------

    @property
    def spec(self) -> CrossbarSpec:
        if self.engine is not None:
            return self.engine.spec
        if self.plan is not None:
            return self.plan.spec
        return self.target.spec or _default_spec(self.target.engine)

    def group_size_for(self, batch: int) -> int:
        """The K the BatchPlanner/executor uses for a ``batch``-slot pool
        (explicit target K > plan WDM capacity > engine capability >
        one vmap'd group; clamped to the pool — and, under fault
        injection, to the surviving WDM lanes)."""
        k = engine_lib.resolve_group_size(
            self.engine, self.target.group_size, batch, plan=self.plan
        )
        cap_fn = getattr(self.engine, "effective_group_cap", None)
        if callable(cap_fn):
            cap = cap_fn()
            if cap is not None:
                k = max(1, min(k, cap))
        return k

    def executor(self, batch: int):
        """The K-grouped execution adapter for a ``batch``-slot pool
        (``None`` on the plain-jnp reference path)."""
        return self._fns(self.group_size_for(batch))[0]

    def _require_params(self):
        if self.params is None:
            raise TargetError(
                "this model was compiled without params (price-only); "
                "re-run compile(cfg, params, target) to execute"
            )

    def _fns(self, k: int):
        """(executor, jitted prefill, jitted decode) per K — cached so a
        steady serving loop traces once."""
        if k not in self._jit:
            from repro.models import layers, lm as lm_lib

            import jax

            ex = (
                engine_lib.GroupedEngine(self.engine, k)
                if self.engine is not None
                else None
            )
            cfg = self.cfg
            exact = layers.EXACT_COMPILER_OPTIONS
            prefill = jax.jit(
                lambda p, t, e: lm_lib.prefill(p, t, cfg, e, engine=ex),
                compiler_options=exact,
            )
            # donate the KV-cache pytree: tick N's caches update in
            # place instead of being copied (decode_step returns the
            # same-shaped new caches, so XLA aliases input to output).
            # Callers must treat the passed caches as CONSUMED and carry
            # the returned pytree forward — every serving loop already
            # does (``logits, caches = decode_step(...)``).
            decode = jax.jit(
                lambda p, t, pos, c: lm_lib.decode_step(p, t, pos, c, cfg, engine=ex),
                donate_argnums=(3,),
                compiler_options=exact,
            )
            self._jit[k] = (ex, prefill, decode)
        return self._jit[k]

    def prefill(self, tokens, extra_embeds=None):
        """Jitted LM prefill through the target's executor:
        (B, S) tokens -> (last-position logits, per-layer caches)."""
        self._require_params()
        _, prefill, _ = self._fns(self.group_size_for(int(tokens.shape[0])))
        return prefill(self.params, tokens, extra_embeds)

    def decode_step(self, token, pos, caches):
        """Jitted single-token decode through the target's executor:
        token (B,), pos scalar or (B,), caches -> (logits, new caches).

        ``caches`` is DONATED: its buffers are updated in place and the
        input pytree must not be reused after the call — carry the
        returned caches forward (``logits, caches = decode_step(...)``).
        """
        self._require_params()
        _, _, decode = self._fns(self.group_size_for(int(token.shape[0])))
        return decode(self.params, token, pos, caches)

    def lower_decode_step(self, token, pos, caches):
        """The ``jax.stages.Lowered`` program :meth:`decode_step` runs on
        these shapes — ``.compile().as_text()`` shows which kernels it
        calls (``tpu_custom_call``). Nothing runs and nothing is donated."""
        self._require_params()
        _, _, decode = self._fns(self.group_size_for(int(token.shape[0])))
        return decode.lower(self.params, token, pos, caches)

    def init_cache(self, batch: int, max_len: int):
        from repro.models import lm as lm_lib

        return lm_lib.init_cache(self.cfg, batch, max_len)

    def graft_prefill_caches(self, caches, pre_caches):
        """Graft prefill-sized caches into a serving-capacity cache
        pytree from :meth:`init_cache` (the one place that knows the
        attn (L,B,T,KV,D) layout grafts by time prefix while ssm states
        carry over whole)."""
        import jax

        def graft(dst, src):
            if dst.ndim == 5 and dst.shape[2] >= src.shape[2]:  # attn (L,B,T,KV,D)
                return dst.at[:, :, : src.shape[2]].set(src.astype(dst.dtype))
            return src.astype(dst.dtype)  # ssm states carry over directly

        return jax.tree.map(graft, caches, pre_caches)

    def serve(self, *, max_batch: int = 4, max_len: int = 256, scheduler=None):
        """A scheduler-fronted continuous-batching ``ServingEngine``
        bound to this model. ``scheduler`` is an optional
        :class:`repro.serving.SchedulerConfig` (policy, admission mode,
        KV reserve ratio, queue cap, preemption) — serve-time knobs,
        deliberately NOT on the compile-time ``HardwareTarget``."""
        self._require_params()
        from repro.serving import ServingEngine  # lazy: serving imports compiler

        return ServingEngine(
            self, max_batch=max_batch, max_len=max_len, scheduler=scheduler
        )

    # -- fault tolerance (PR 9) ---------------------------------------------

    def _fault_engine(self):
        from repro.faults.engine import FaultyEngine

        return self.engine if isinstance(self.engine, FaultyEngine) else None

    def _fault_artifacts(self):
        """Every resident PreparedWeights in the programmed params."""
        if self.params is None:
            return []
        import jax

        leaf = lambda x: isinstance(x, engine_lib.PreparedWeights)  # noqa: E731
        return [
            pw for pw in jax.tree.leaves(self.params, is_leaf=leaf) if leaf(pw)
        ]

    def _refresh_artifacts(self):
        """Re-derive every resident artifact under the wrapper's CURRENT
        fault state / inner engine (the reprogramming step)."""
        import jax

        eng = self.engine
        leaf = lambda x: isinstance(x, engine_lib.PreparedWeights)  # noqa: E731
        return jax.tree.map(
            lambda x: eng.refresh(x) if leaf(x) else x, self.params, is_leaf=leaf
        )

    def scan_faults(self):
        """One consistency sweep over all resident artifacts: the
        :class:`repro.faults.FaultMap` of physical tiles holding
        corrupted cells plus the dead WDM lanes. Empty (falsy) on a
        non-fault-injecting target."""
        from repro.faults import FaultMap

        eng = self._fault_engine()
        if eng is None:
            return FaultMap()
        tiles: frozenset[int] = frozenset()
        for pw in self._fault_artifacts():
            tiles |= eng.locate(pw)
        if tiles:
            obs.count(
                "repro_faults_detected_total", len(tiles),
                "faulty physical tiles flagged by consistency sweeps",
            )
        return FaultMap(tiles=tiles, lanes=eng.dead_lanes())

    def refresh_faults(self) -> None:
        """Reprogram all artifacts after the fault state changed
        (``engine.fail_tile`` / ``engine.advance_drift``) so execution
        observes the new state."""
        if self._fault_engine() is None:
            raise TargetError(
                "refresh_faults() needs a fault-injecting target "
                "(HardwareTarget(fault_model=...))"
            )
        if self.params is not None:
            self.params = self._refresh_artifacts()
        self._jit.clear()

    def remap(self, fault_map) -> "RemapReport":
        """Move ONLY the blocks resident on the fault map's tiles onto
        clean spares, rebind the (re-placed) inner engine under the same
        fault state, and reprogram just the refreshed artifacts.

        Raises :class:`repro.mapping.SpareTilesExhaustedError` when the
        clean-spare pool can't cover the displaced blocks, and
        :class:`TargetError` when the target has no fault wrapper or no
        plan to re-place."""
        from repro.mapping import remap_plan

        eng = self._fault_engine()
        if eng is None:
            raise TargetError(
                "remap() needs a fault-injecting target "
                "(HardwareTarget(fault_model=...))"
            )
        if self.plan is None:
            raise TargetError(
                "remap() re-places an explicit MappingPlan — compile with "
                "the 'tiled' engine and spare_tiles/mapping_policy set"
            )
        tiles = frozenset(getattr(fault_map, "tiles", fault_map))
        with obs.span("remap", track="compile", tiles=sorted(tiles)) as sp:
            new_plan, delta = remap_plan(
                self.plan, tiles, tile_ok=eng.tile_is_clean
            )
            inner = resolve_engine(
                dataclasses.replace(self.target, fault_model=None),
                self.cfg, new_plan,
            )
            self.plan = new_plan
            self._price_plan = new_plan
            self.engine = eng.rebind(inner)
            if self.params is not None:
                self.params = self._refresh_artifacts()
            # cached executors close over the OLD wrapper — drop them
            self._jit.clear()
            sp.set(moves=len(delta.moves), spares_left=len(new_plan.spares))
        obs.count("repro_remaps_total", 1, "fault-driven incremental remaps")
        return RemapReport(
            moves=delta.moves,
            cost=delta.cost,
            failed_tiles=tiles,
            spares_left=len(new_plan.spares),
        )

    # -- pricing / reporting ------------------------------------------------

    def _pricing_plan(self):
        """The plan the cost model prices: the bound plan, else one
        compiled lazily on the target's spec/policy (pricing is static —
        a reference/wdm target still prices the paper's mapping)."""
        if self._price_plan is None:
            from repro.mapping import compile_plan

            self._price_plan = compile_plan(
                self.cfg,
                spec=self.target.spec or self.spec,
                policy=self.target.mapping_policy
                or getattr(self.cfg, "mapping_policy", None)
                or "tacitmap",
                tile_budget=self.target.tile_budget,
            )
        return self._price_plan

    def pricing_plan(self):
        """Public accessor for the plan the cost model prices (the bound
        plan, else one compiled lazily on the target's spec/policy).
        The telemetry cross-check (:mod:`repro.obs.crosscheck`) uses it
        to price traced decode ticks."""
        return self._pricing_plan()

    def price(self, n_active: int = 16) -> TargetPrice:
        """Plan execution + one-time programming + per-tick readout, in
        one report (``n_active`` = serving slots per decode tick)."""
        from repro.core import costmodel

        plan = self._pricing_plan()
        cost = costmodel.price_plan(plan)
        prog = costmodel.plan_programming_cost(plan)
        tick = costmodel.plan_decode_tick(plan, n_active)
        return TargetPrice(
            target=self.target,
            design=cost.design,
            policy=plan.policy,
            n_tiles=plan.n_tiles,
            utilization=plan.utilization(),
            k=plan.preferred_group_size(),
            binary_steps=cost.binary_steps,
            latency_s=cost.latency_s,
            energy_j=cost.energy_j,
            programming_cells=prog.cells,
            programming_uj=prog.energy_pj * 1e-6,
            programming_us=prog.time_ns * 1e-3,
            tick_latency_ns=tick.latency_ns,
            tick_energy_pj=tick.energy_pj,
            break_even_ticks=prog.energy_pj / max(tick.energy_pj, 1e-12),
            plan_cost=cost,
        )

    def describe(self, max_rows: int = 12) -> str:
        """Placement + pricing tables for this target (mapping.report)."""
        from repro.mapping import report

        plan = self._pricing_plan()
        price = self.price()  # carries the plan_cost format_priced needs
        lines = [self.target.describe(), report.summarize(plan)]
        lines.append(report.format_priced(price.plan_cost))
        lines.append(price.summary())
        if self.programmed:
            lines.append(
                f"[program] {self.programmed} projection instance(s) resident "
                f"in {self.target.engine} form ({self.program_s * 1e3:.1f} ms "
                "one-time PCM write)"
            )
        return "\n".join(lines)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        eng = self.engine.name if self.engine is not None else "reference"
        planned = self.plan.policy if self.plan is not None else "-"
        return (
            f"<CompiledModel {self.cfg.name} engine={eng} plan={planned} "
            f"programmed={self.programmed}>"
        )
