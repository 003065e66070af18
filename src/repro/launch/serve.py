"""Batched serving driver: prefill a batch of prompts, then decode with
a fixed-capacity KV/SSM cache.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --batch 4 --prompt-len 32 --gen 16

    # serve the binarized projections through the packed Pallas kernel:
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --engine packed

    # the scheduler-fronted request path: 12 staggered requests through
    # admission control + deadline policy, reported as typed stats:
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --requests 12 --sched-policy deadline --kv-reserve 0.1

Uses the same decode_step the dry-run lowers for the ``decode_*``
cells, so serving on the production mesh is the identical program.

Execution is driven through the one-call hardware-compilation API
(``repro.compiler``): the shared target flags (``--engine``,
``--group-size``, ``--mapping-policy``, ``--tile-budget``,
``--raw-weights`` — installed by ``compiler.add_target_args``) build ONE
:class:`~repro.compiler.HardwareTarget`, and
``compile(cfg, params, target)`` runs plan compilation, engine
resolution and the one-time crossbar-programming phase in the canonical
order. What used to be five separately-threaded knobs::

    eng = get_engine(args.engine, plan=plan, policy=policy)
    cfg = replace(cfg, quant="bnn", bnn_engine=args.engine)
    k = resolve_group_size(eng, args.group_size, args.batch, plan=plan)
    grouped = GroupedEngine(eng, k)
    params, n = lm_lib.program_weights(params, cfg, grouped)

is now::

    compiled = compiler.compile(cfg, params, target_from_args(args))

``--mapping-policy`` (with ``--engine tiled``) compiles the arch's
binarized projections into an explicit layer->tile MappingPlan and
prints the placement summary + cost-model pricing
(``compiled.describe()``):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --smoke --engine tiled --mapping-policy greedy
"""

from __future__ import annotations

import argparse
import dataclasses
import time


def _finish_obs(tel, args, se=None) -> None:
    """Export the telemetry session's artifacts (and, when a serving
    engine ran traced decode ticks, print the measured-vs-modeled
    pricing cross-check). No-op when telemetry is off."""
    if tel is None:
        return
    from repro import obs

    if se is not None and tel.tracer.spans("decode_tick"):
        print("[obs] measured-vs-modeled decode-tick pricing:")
        print(obs.format_report(obs.crosscheck_serving(se, tracer=tel.tracer)))
    tel.write(trace_out=args.trace_out, metrics_out=args.metrics_out)
    if args.trace_out:
        print(f"[obs] wrote Chrome trace (chrome://tracing / Perfetto) -> "
              f"{args.trace_out}")
    if args.metrics_out:
        print(f"[obs] wrote Prometheus-style metrics snapshot -> "
              f"{args.metrics_out}")
    obs.stop()


def _serve_requests(compiled, args, tel=None) -> int:
    """The scheduler-fronted path: N requests with staggered prompt
    lengths through ``submit``/``drain``, reported as typed stats."""
    import numpy as np

    from repro import compiler as compiler_lib
    from repro.data import lm_batch
    from repro.serving import Request

    max_len = args.prompt_len + args.gen
    se = compiled.serve(
        max_batch=args.batch,
        max_len=max_len,
        scheduler=compiler_lib.scheduler_from_args(args),
    )
    tokens = lm_batch(compiled.cfg, args.requests, args.prompt_len,
                      seed=args.seed)["tokens"]
    # the synthetic arrival trace (staggered prompt lengths) draws from
    # its own seed so load patterns reproduce independently of model
    # init; it falls back to --seed when unset
    trace_seed = args.request_seed if args.request_seed is not None else args.seed
    rng = np.random.default_rng(trace_seed)
    states = []
    t0 = time.time()
    for i in range(args.requests):
        # staggered prompt lengths: the scheduler's budget math and
        # K-group planner see a ragged, realistic mix
        plen = int(rng.integers(max(1, args.prompt_len // 2), args.prompt_len + 1))
        states.append(se.submit(Request(
            rid=i,
            prompt=np.asarray(tokens[i, :plen], np.int32),
            max_new_tokens=args.gen,
        )))
    se.drain()
    wall = time.time() - t0

    st = se.stats()
    sch = st.scheduler
    toks = sum(len(s.generated) for s in states)
    print(f"[serve] scheduler: policy={sch.policy} admission={sch.admission} "
          f"K={se.group_k} pool={args.batch}x{max_len} "
          f"(kv budget {sch.kv_budget}, usable {sch.kv_usable})")
    print(f"[serve] drained {args.requests} request(s) in {wall*1e3:.1f} ms "
          f"({toks / max(wall, 1e-9):.1f} tok/s): finished={sch.finished} "
          f"rejected={sch.rejected} expired={sch.expired} "
          f"preempted={sch.preempted} resumed={sch.resumed}")
    print(f"[serve] ticks={st.ticks} decoded={st.decoded} "
          f"mmm_groups={st.mmm_groups} pad_lanes={st.pad_lanes} "
          f"prefills={st.prefills} evictions={st.evictions}")
    print(f"[serve] ttft={sch.ticks_to_first_token:.2f} ticks, "
          f"admission wait={sch.admission_wait_ticks:.2f} ticks, "
          f"max queue depth={sch.max_queue_depth}")
    done = [s for s in states if s.done]
    if done:
        head = done[0]
        print(f"[serve] rid={head.rid} generated[:8] = {head.generated[:8]}")
    _finish_obs(tel, args, se=se)
    return 0


def _serve_fleet(cfg, params, target, args, tel=None) -> int:
    """The fleet path (``--replicas > 1``): N identically-compiled
    replicas behind the prefix-affinity router, driven by the same
    staggered synthetic request trace as the single-replica scheduler
    path (one arrival per fleet tick, so the prefix library is live
    for later arrivals)."""
    import numpy as np

    from repro import compiler as compiler_lib
    from repro.data import lm_batch
    from repro.fleet import FleetEngine
    from repro.serving import Request

    max_len = args.prompt_len + args.gen
    fleet = FleetEngine.build(
        cfg, params, target,
        n_replicas=args.replicas,
        max_batch=args.batch,
        max_len=max_len,
        scheduler=compiler_lib.scheduler_from_args(args),
        routing=args.routing,
        block_size=args.prefix_block,
    )
    tokens = lm_batch(cfg, args.requests, args.prompt_len,
                      seed=args.seed)["tokens"]
    trace_seed = args.request_seed if args.request_seed is not None else args.seed
    rng = np.random.default_rng(trace_seed)
    states = []
    t0 = time.time()
    for i in range(args.requests):
        plen = int(rng.integers(max(1, args.prompt_len // 2), args.prompt_len + 1))
        states.append(fleet.submit(Request(
            rid=i,
            prompt=np.asarray(tokens[i, :plen], np.int32),
            max_new_tokens=args.gen,
        )))
        fleet.step()
    fleet.drain()
    wall = time.time() - t0

    s = fleet.stats()
    toks = sum(len(st.generated) for st in states)
    print(f"[fleet] {s.n_replicas} replica(s) x {args.batch} slot(s), "
          f"routing={s.routing} (block={args.prefix_block})")
    print(f"[fleet] drained {args.requests} request(s) in {wall*1e3:.1f} ms "
          f"({toks / max(wall, 1e-9):.1f} tok/s): finished={s.finished} "
          f"rejected={s.rejected} expired={s.expired} failed={s.failed}")
    print(f"[fleet] prefix hits={s.prefix_hits} "
          f"(rate {s.prefix_hit_rate:.0%}), grafted={s.grafted_tokens} "
          f"prefilled={s.prefill_tokens} prompt tokens; "
          f"failovers={s.failovers} (salvaged={s.salvaged}), "
          f"healthy={s.healthy_replicas}/{s.n_replicas}")
    per = ", ".join(
        f"r{i}: {r.ticks}t/{r.decoded}d" for i, r in enumerate(s.replicas)
    )
    print(f"[fleet] per-replica ticks/decoded: {per}")
    print(fleet.price(n_active=args.batch).summary())
    done = [st for st in states if st.done]
    if done:
        head = done[0]
        print(f"[fleet] rid={head.rid} replica={head.replica} "
              f"generated[:8] = {head.generated[:8]}")
    _finish_obs(tel, args)
    return 0


def main(argv: list[str] | None = None) -> int:
    from repro import compiler as compiler_lib
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--requests",
        type=int,
        default=0,
        metavar="N",
        help="drive the request scheduler with N independent requests "
        "(staggered prompt lengths, admission control, typed stats) "
        "instead of the lockstep batch loop",
    )
    ap.add_argument(
        "--request-seed",
        type=int,
        default=None,
        metavar="SEED",
        help="seed of the synthetic request trace (staggered prompt "
        "lengths) alone, so arrival patterns reproduce independently "
        "of model init; defaults to --seed",
    )
    # the shared hardware-target surface (engine / K / mapping / prepare)
    compiler_lib.add_target_args(ap)
    # the serve-time scheduler surface (policy / admission / KV reserve)
    compiler_lib.add_scheduler_args(ap)
    # the fleet surface (--replicas / --routing / --prefix-block)
    compiler_lib.add_fleet_args(ap)
    # the telemetry surface (--trace-out / --metrics-out)
    compiler_lib.add_obs_args(ap)
    args = ap.parse_args(argv)
    try:
        target = compiler_lib.target_from_args(args)
    except compiler_lib.TargetError as e:
        ap.error(str(e))
    if target.engine == "reference" and target.group_size:
        # (the serving engine's BatchPlanner can group the plain-jnp
        # path; this batch driver only groups through a backend)
        ap.error("--group-size requires a non-reference --engine")

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, get_smoke_config
    from repro.data import lm_batch
    from repro.models import encdec as encdec_lib
    from repro.models import lm as lm_lib

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if target.engine == "tiled" and target.mapping_policy is None:
        # `--engine tiled` always executes per an explicit compiled plan
        # here; the policy falls back to the arch config's default
        target = dataclasses.replace(target, mapping_policy=cfg.mapping_policy)
    if cfg.is_encdec:
        # the compiler pipeline covers the decoder-only LM projection
        # stack; enc-dec archs bind the backend via cfg.bnn_engine and
        # reject the decoder-only-serving knobs
        if target.group_size:
            ap.error("--group-size applies to the decoder-only serving path")
        if target.wants_plan:
            ap.error("--mapping-policy/--tile-budget place weights for the "
                     "decoder-only LM projection stack")
        if not target.prepare_weights:
            ap.error("--raw-weights toggles the decoder-only compile "
                     "pipeline's programming phase; the enc-dec path never "
                     "programs weights")
        if target.engine != "reference":
            cfg = dataclasses.replace(cfg, quant="bnn", bnn_engine=target.engine)
            from repro.core import engine as engine_lib

            eng = engine_lib.get_engine(target.engine)
            print(f"[serve] engine={eng.name} ({eng.info.description})")

    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.replicas > 1:
        if cfg.is_encdec:
            ap.error("--replicas drives the decoder-only fleet path")
        if not args.requests:
            ap.error("--replicas > 1 serves the request path; pass --requests N")

    # the telemetry session must be live BEFORE compile() so the
    # pipeline-stage spans (validate/map/resolve/program) are captured
    tel = compiler_lib.obs_from_args(args)

    max_len = args.prompt_len + args.gen
    key = jax.random.key(args.seed)
    params = (
        encdec_lib.init_params(key, cfg) if cfg.is_encdec else lm_lib.init_params(key, cfg)
    )
    if args.replicas > 1:
        # each replica compiles and programs its own copy of the target
        # inside FleetEngine.build — skip the solo compile entirely
        return _serve_fleet(cfg, params, target, args, tel=tel)
    compiled = None
    if not cfg.is_encdec:
        # the one-call pipeline: map (plan) -> resolve (engine) ->
        # program (one-time PCM write); raises named TargetErrors on
        # inconsistent combinations instead of dropping knobs
        try:
            compiled = compiler_lib.compile(cfg, params, target)
        except compiler_lib.TargetError as e:
            ap.error(str(e))
        cfg, params = compiled.cfg, compiled.params
        if compiled.engine is not None:
            print(f"[serve] engine={compiled.engine.name} "
                  f"({compiled.engine.info.description})")
            if compiled.plan is not None:
                print(compiled.describe())
            k = compiled.group_size_for(args.batch)
            print(f"[serve] K-group batching: K={k}, "
                  f"{-(-args.batch // k)} group(s)/tick over batch={args.batch}, "
                  f"idle lanes/tick={-(-args.batch // k) * k - args.batch}")
            if compiled.programmed:
                print(f"[serve] programmed {compiled.programmed} binarized "
                      f"projection instance(s) into {target.engine} resident "
                      f"form ({compiled.program_s * 1e3:.1f} ms, one-time PCM write)")
    if args.requests:
        # scheduler-fronted request path: N independent requests with
        # staggered prompt lengths through submit/drain + typed stats
        if cfg.is_encdec:
            ap.error("--requests drives the decoder-only scheduler path")
        return _serve_requests(compiled, args, tel=tel)

    batch = lm_batch(cfg, args.batch, args.prompt_len, seed=args.seed)
    tokens = batch["tokens"]

    t0 = time.time()
    if cfg.is_encdec:
        logits, pre_caches = jax.jit(
            lambda p, s, t: encdec_lib.prefill(p, s, t, cfg)
        )(params, batch["src_embeds"], tokens)
        caches = encdec_lib.init_cache(cfg, args.batch, max_len, cfg.frontend_len)
        # copy prompt KV into the serving-capacity cache
        caches = dict(
            caches,
            cross_k=pre_caches["cross_k"],
            cross_v=pre_caches["cross_v"],
            self_k=caches["self_k"].at[:, :, : args.prompt_len].set(pre_caches["self_k"]),
            self_v=caches["self_v"].at[:, :, : args.prompt_len].set(pre_caches["self_v"]),
        )
        decode = jax.jit(lambda p, t, pos, c: encdec_lib.decode_step(p, t, pos, c, cfg))

        def decode_step(tok, pos, caches):
            return decode(params, tok, pos, caches)
    else:
        logits, pre_caches = compiled.prefill(tokens, batch.get("extra_embeds"))
        caches = compiled.graft_prefill_caches(
            compiled.init_cache(args.batch, max_len), pre_caches
        )
        decode_step = compiled.decode_step
    # fence the phase: JAX dispatch is async, so without block_until_ready
    # this would time the enqueue, not the prefill + cache graft
    jax.block_until_ready((logits, caches))
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    # positions continue after the prompt (+ any frontend prefix)
    base = args.prompt_len + (cfg.frontend_len if cfg.frontend == "vision" else 0)
    for i in range(args.gen - 1):
        logits, caches = decode_step(tok, jnp.asarray(base + i, jnp.int32), caches)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    # fence tokens AND the final cache state — the decode phase isn't
    # done until its last KV write lands
    jax.block_until_ready((out[-1], caches))
    t_decode = time.time() - t0

    gen = jnp.stack(out, axis=1)
    print(f"[serve] arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"quant={cfg.quant} engine={cfg.bnn_engine}")
    print(f"[serve] prefill {t_prefill*1e3:.1f} ms; decode {args.gen - 1} steps "
          f"{t_decode*1e3:.1f} ms ({(args.gen - 1) * args.batch / max(t_decode, 1e-9):.1f} tok/s)")
    if compiled is not None and compiled.engine is not None and args.gen > 1:
        k = compiled.group_size_for(args.batch)
        ticks = args.gen - 1
        groups = ticks * -(-args.batch // k)
        slot_steps = ticks * args.batch
        print(f"[serve] batched path: K={k}, 1 binary_mmm call/projection/tick, "
              f"{groups} K-groups over {ticks} ticks "
              f"(vs {slot_steps} slot-at-a-time steps, {slot_steps / groups:.1f}x fewer)")
    print(f"[serve] generated[0,:8] = {gen[0, :8].tolist()}")
    _finish_obs(tel, args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
