"""Drives one cell of the benchmark once: set-up, a timed window, a check.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
cell names a configuration (``configs/<name>.json``: the model's sizes
in their published keys, and the program entry it runs on) and a
traffic mix (``traffic/<name>.json``, read by ``traffic.py``); each
metric is a reader ``metrics/<name>.py`` with ``read(run) -> float |
None``. A new cell or metric is new files and entries, never an edit.

The timed window drives the program's served path,
``repro.compiler.compile(cfg, params, HardwareTarget(engine="packed"))
.serve(max_batch, max_len)``, through its scheduler's ``submit`` and
``step``: the harness submits each request when it falls due, steps,
and stamps every token on the host clock from the ``on_token``
callback. Every request is timed from when it was due.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import statistics
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
DRAIN_S = 60.0        # how long past the window a request may still finish
TRACE_S = 4.0         # the profiled stretch of a traced run


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the specification, found by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Cell:
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    def metrics(self, trace: bool) -> list[dict]:
        """The cell's end-to-end metrics, or with ``trace`` its per-layer
        ones: those listing this cell, or listing none and moving an
        end-to-end metric that the cell reports."""
        e2e = [m for m in self.end_to_end if self.name in m.get("workloads", [self.name])]
        if not trace:
            return e2e
        moved = {m["name"] for m in e2e}
        return [
            m for m in self.per_layer
            if self.name in m.get("workloads", [self.name] if m["moves"] in moved else [])
        ]


def load_cell(name: str, root: Path = ROOT, traffic_dir: Path = HERE / "traffic") -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    workloads = {w["name"]: w for w in spec["workloads"]}
    if name not in workloads:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(workloads)}")
    wl = workloads[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[wl["config"]]["file"]).read_text())
    from traffic import load as load_traffic

    return Cell(
        workload=wl,
        config=config,
        traffic=load_traffic(wl["traffic"], traffic_dir),
        end_to_end=spec["end_to_end"],
        per_layer=spec["per_layer"],
    )


def reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs import get_config

    prog = config["program"]
    base = get_config(prog["arch"], quant="bnn")
    heads = config["num_attention_heads"]
    return dataclasses.replace(
        base,
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim", 0) or 0,
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        qkv_bias=True,
    )


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Req:
    rid: int
    due: float
    prompt: np.ndarray
    max_new: int
    state: object = None          # the scheduler's RequestState
    submitted: float = 0.0
    admitted: float | None = None
    token_t: list = dataclasses.field(default_factory=list)

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    prefills: list            # prompt lengths prefilled in this step
    decoded: list             # (prompt_len, token index) of each decoded token


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    cell: Cell
    dims: object
    peak: dict
    max_batch: int
    setup_s: float
    t0: float                 # window start (host clock)
    window_s: float           # the measured window
    requests: list            # Req of every request due in the window
    steps: list               # Step of every step in the window
    stats0: object = None     # ServingStats at the window's start and end
    stats1: object = None
    spans: dict = dataclasses.field(default_factory=dict)   # name -> [s]
    trace: dict | None = None     # trace.reduce() of the profiled stretch
    traced_steps: list = dataclasses.field(default_factory=list)


def quantile(values, q: float) -> float:
    """Nearest-rank quantile (q in (0, 1])."""
    v = sorted(values)
    return v[max(0, int(np.ceil(q * len(v))) - 1)]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class Driver:
    """Submits requests, steps the scheduler, stamps tokens."""

    def __init__(self, se, mix: dict, seed: int, vocab: int):
        self.se, self.mix, self.seed, self.vocab = se, mix, seed, vocab
        self.reqs: dict[int, Req] = {}
        self.steps: list[Step] = []
        self._unadmitted: list[Req] = []
        self._events: list = []
        self.annotate = None      # a context factory while profiling

    def request(self, rid: int, due: float, length: int, max_new: int) -> Req:
        from traffic import request_prompt

        p = request_prompt(self.mix, self.seed, rid, length, self.vocab)
        return Req(rid=rid, due=due, prompt=p, max_new=max_new)

    def _on_token(self, rid: int, token: int, index: int) -> None:
        t = time.perf_counter()
        req = self.reqs[rid]
        req.token_t.append(t)
        self._events.append((req.prompt_len, index))

    def submit(self, req: Req, now: float) -> None:
        from repro.serving import Request

        self.reqs[req.rid] = req
        req.submitted = now
        req.state = self.se.submit(Request(
            rid=req.rid, prompt=req.prompt, max_new_tokens=req.max_new,
            on_token=self._on_token,
        ))
        self._unadmitted.append(req)

    def step(self) -> list:
        t0 = time.perf_counter()
        if self.annotate is None:
            done = self.se.step()
        else:
            with self.annotate("bench.step"):
                done = self.se.step()
        t1 = time.perf_counter()
        waiting = []
        for req in self._unadmitted:
            if getattr(req.state, "admitted_tick", None) is None:
                waiting.append(req)
            else:
                req.admitted = t0  # the stamp of the tick that admitted it
        self._unadmitted = waiting
        ev, self._events = self._events, []
        self.steps.append(Step(
            t0, t1,
            prefills=[p for p, i in ev if i == 0],
            decoded=[(p, i) for p, i in ev if i > 0],
        ))
        return done



def warm_up(pool, mix: dict, seed: int, vocab: int) -> None:
    """Compile or load every program one slot pool will run for the
    mix: each prompt length prefilled into every slot (the prefill
    program per length and the eager graft per slot and length), then
    both decode dispatches at every count of live slots."""
    from repro.serving import Request
    from traffic import prompt, prompt_lengths

    b = pool.max_batch
    lens = prompt_lengths(mix)
    rid = -1
    for length in lens:
        for _ in range(b):
            pool.submit(Request(rid=rid, prompt=prompt(seed, 2**31 + rid, length, vocab),
                                max_new_tokens=1))
            rid -= 1
        pool.drain()
    for i in range(b):
        pool.submit(Request(rid=rid, prompt=prompt(seed, 2**31 + rid, lens[0], vocab),
                            max_new_tokens=2 + i))
        rid -= 1
    pool.drain()


def warm_traffic(server, mix: dict, seed: int, vocab: int) -> None:
    """Serve ``warm_requests`` of the mix itself, from a seed stream the
    window never uses, through the whole server (router included)."""
    from traffic import closed_loop_sizes

    stream = seed + 2**62
    drv = Driver(server, mix, stream, vocab)
    for i, (p, g) in enumerate(closed_loop_sizes(mix, stream, mix.get("warm_requests", 0))):
        drv.submit(drv.request(i, 0.0, p, g), 0.0)
    server.drain()


def serve(cell: Cell, seed: int, dims):
    """Weights from the seed, ``compile()`` for the packed target, and
    the server of the mix: one slot pool, or with ``replicas`` a fleet
    of one-chip replicas behind the router (``FleetEngine.build``);
    every shape of its traffic warmed."""
    import weights
    from repro import compiler

    mix = cell.traffic
    cfg = model_config(cell.config)
    target = compiler.HardwareTarget(engine="packed")
    params = weights.program_params(weights.table_keys(seed), dims)
    if mix.get("replicas", 1) > 1:
        from repro.fleet import FleetEngine

        server = FleetEngine.build(
            cfg, params, target, n_replicas=mix["replicas"], max_batch=mix["max_batch"],
            max_len=mix["max_len"], routing=mix.get("routing", "prefix"),
        )
        pools = [rep.serving for rep in server.replicas.values()]
    else:
        server = compiler.compile(cfg, params, target).serve(
            max_batch=mix["max_batch"], max_len=mix["max_len"]
        )
        pools = [server]
    del params
    for pool in pools:
        warm_up(pool, mix, seed, dims.vocab)
    warm_traffic(server, mix, seed, dims.vocab)
    return server


def _compile_counter():
    """Counts backend compiles from now on (JAX's monitoring events)."""
    import jax

    box = {"compiles": 0}

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            box["compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return box


def run(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float | None = None,
    require_chip: bool = True,
    root: Path = ROOT,
    traffic_dir: Path = HERE / "traffic",
) -> dict:
    """One run of one cell; returns the result line as a dict."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(workload, root, traffic_dir)
    import jax

    devices = jax.devices()
    chips = cell.workload["chips"]
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(
            f"{workload} needs {chips} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform} device(s)"
        )
    device = devices[0]
    import costs
    import reference

    peak = costs.peaks(device.device_kind) if require_chip else costs.peaks("TPU v5 lite")
    if require_chip:
        from repro.launch.compile_cache import use_compile_cache

        log(f"compile cache: {use_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro import obs

    mix, config = cell.traffic, cell.config
    dims = reference.Dims.from_config(config)
    b, max_len = mix["max_batch"], mix["max_len"]
    se = serve(cell, seed, dims)
    counter = _compile_counter()

    drv = Driver(se, mix, seed, dims.vocab)
    setup_s = time.perf_counter() - t_start
    stats0 = se.stats()
    if trace:
        tel = obs.start()
    t0 = time.perf_counter()
    profile = _Profile(root / ".bench_out" / "trace", t0, seconds) if trace else None
    if mix["loop"] == "open":
        window = open_loop_window(drv, mix, seed, seconds, t0, profile)
    else:
        window = closed_loop_window(drv, mix, seed, seconds, t0, profile)
    if trace:
        spans = {n: [s.duration_s for s in tel.tracer.spans(n)] for n in ("prefill", "decode_tick")}
        obs.stop()
    stats1 = se.stats()
    compiles_in_window = counter["compiles"]
    late = drain(drv, window["requests"], t0 + seconds)
    # the peak on the fullest chip the cell uses
    memory_peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[:chips]
    )

    reqs = window["requests"]
    sample = _check_sample(reqs, seed, mix["check_requests"])
    prompts = [r.prompt for r in sample]
    served = [np.asarray(r.state.generated, np.int32) for r in sample]
    short = [r.rid for r in sample if len(r.state.generated) != r.max_new]
    n_tokens = sum(len(s) for s in served)
    del se, drv
    gc.collect()

    run_ = Run(
        cell=cell, dims=dims, peak=peak, max_batch=b, setup_s=setup_s, t0=t0,
        window_s=window["window_s"], requests=reqs, steps=window["steps"],
        stats0=stats0, stats1=stats1,
    )
    trace_summary = None
    if trace:
        run_.spans = spans
        run_.trace = trace_summary = profile.reduce()
        run_.traced_steps = profile.steps

    t_check = time.perf_counter()
    checker = reference.Checker(dims, seed, b, max_len)
    gap = checker.gaps(prompts, served)["served_gap"]
    check_s = time.perf_counter() - t_check
    limit = config["check"]["max_logit_gap"]
    correct = gap <= limit and not short and len(sample) > 0

    failed = sum(1 for r in reqs if not _finished(r))
    metrics = {}
    for m in cell.metrics(trace):
        value = reader(m["name"])(run_)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    _report(run_, window, late, compiles_in_window, check_s, len(sample), n_tokens)
    check = {
        "served_logit_gap": {"value": gap, "limit": limit},
        "checked_requests": {"value": len(sample), "limit": 1},
        "short_requests": {"value": len(short), "limit": 0},
    }
    for name, c in check.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    dev = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(memory_peak),
    }
    result = {
        "correct": bool(correct),
        "attempted": len(reqs),
        "failed": failed,
        "metrics": metrics,
        "device": dev,
    }
    if trace_summary is not None:
        dev["busy_s"] = trace_summary["busy_s"]
        dev["window_s"] = trace_summary["window_s"]
        result["breakdown"] = {
            "device_ops": trace_summary["device_ops"],
            "idle_gaps": trace_summary["idle_gaps"],
        }
    result["check"] = check
    return result


def _finished(req: Req) -> bool:
    st = req.state
    return st is not None and getattr(st.status, "value", "") == "finished"


class _Profile:
    """The profiled stretch of a traced run: a few seconds from 40% of
    the window, started and stopped between steps."""

    def __init__(self, directory: Path, t0: float, seconds: float):
        self.dir = Path(directory)
        self.start_at = t0 + 0.4 * seconds
        self.stop_at = self.start_at + min(TRACE_S, 0.4 * seconds)
        self.state = "before"
        self.steps: list[Step] = []
        self._window = None

    def tick(self, drv: Driver, now: float) -> float:
        """Start or stop the profiler when due; returns the seconds that
        took, which the window then leaves out."""
        import jax

        if self.state == "before" and now >= self.start_at:
            import shutil

            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True)
            # no Python function tracing: it slows the host several
            # times over and the generator falls behind
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self._window = jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()
            drv.annotate = jax.profiler.TraceAnnotation
            self._first = len(drv.steps)
            self.state = "on"
            return time.perf_counter() - now
        if self.state == "on" and now >= self.stop_at:
            return self.stop(drv)
        return 0.0

    def stop(self, drv: Driver) -> float:
        import jax

        if self.state != "on":
            return 0.0
        t = time.perf_counter()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        drv.annotate = None
        self.steps = drv.steps[self._first:]
        self.state = "done"
        return time.perf_counter() - t

    def reduce(self) -> dict:
        import devtrace as trace_lib

        plain = trace_lib.from_xplane(str(self.dir))
        summary = trace_lib.reduce(plain, {"bitlinear": ("_fused_kernel", "fused_bnn")})
        import shutil

        shutil.rmtree(self.dir, ignore_errors=True)
        return summary


def _annotated(drv: Driver, name: str):
    import contextlib

    return drv.annotate(name) if drv.annotate is not None else contextlib.nullcontext()


def open_loop_window(drv: Driver, mix: dict, seed: int, seconds: float, t0: float, profile) -> dict:
    """Poisson arrivals at the mix's fixed rate; the window ends at
    ``t0 + seconds``, and requests due in it are drained after."""
    from traffic import open_loop

    reqs = [drv.request(i, t0 + off, p, g) for i, (off, p, g) in enumerate(open_loop(mix, seed, seconds))]
    nxt = 0
    end = t0 + seconds
    while True:
        now = time.perf_counter()
        if profile is not None:
            paused = profile.tick(drv, now)
            if paused:
                # the schedule waits while the profiler starts or stops
                for r in reqs[nxt:]:
                    r.due += paused
                end += paused
                now = time.perf_counter()
        while nxt < len(reqs) and reqs[nxt].due <= now:
            with _annotated(drv, "bench.submit"):
                drv.submit(reqs[nxt], now)
            nxt += 1
        if now >= end and nxt == len(reqs):
            break
        if drv.se.idle():
            wait = (reqs[nxt].due if nxt < len(reqs) else end) - now
            with _annotated(drv, "bench.wait"):
                time.sleep(max(0.0, wait))
            continue
        drv.step()
    if profile is not None:
        profile.stop(drv)
    return {
        "requests": reqs, "steps": list(drv.steps), "window_s": seconds,
        "offered_rate": len(reqs) / seconds,
    }


def closed_loop_window(drv: Driver, mix: dict, seed: int, seconds: float, t0: float, profile) -> dict:
    """``clients`` callers, each sending its next request as soon as its
    last one finished; the window is every step begun before
    ``t0 + seconds``."""
    from traffic import closed_loop_sizes

    sizes = closed_loop_sizes(mix, seed, 4096)
    reqs: list[Req] = []

    def send(now: float) -> None:
        req = drv.request(len(reqs), now, *sizes[len(reqs)])
        reqs.append(req)
        with _annotated(drv, "bench.submit"):
            drv.submit(req, now)

    now = t0
    for _ in range(mix["clients"]):
        send(now)
    end = t0 + seconds
    paused = 0.0
    while now < end:
        if profile is not None:
            p = profile.tick(drv, now)
            paused += p
            end += p
        done = drv.step()
        now = time.perf_counter()
        for _ in done:
            if now < end:
                send(now)
    if profile is not None:
        paused += profile.stop(drv)
    return {"requests": reqs, "steps": list(drv.steps), "window_s": now - t0 - paused}


def drain(drv: Driver, reqs: list, close: float) -> float:
    """Step until every request of the window is terminal, or until
    ``DRAIN_S`` past the close; returns how late the generator ran at
    most (submit minus due)."""
    while not drv.se.idle() and time.perf_counter() < close + DRAIN_S:
        drv.step()
    return max((r.submitted - r.due for r in reqs), default=0.0)


def _check_sample(reqs: list, seed: int, n: int) -> list:
    """Finished requests to check: the longest, and others drawn from
    the seed."""
    done = [r for r in reqs if _finished(r)]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.state.generated), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed % 2**64, 3])
    picked = [rest[i] for i in sorted(rng.permutation(len(rest))[: n - 1])] if rest else []
    return [longest] + picked


def _report(run_: Run, window: dict, late: float, compiles: int, check_s: float,
            n_checked: int, n_tokens: int) -> None:
    """Counts and medians, on lines before the result."""
    reqs = run_.requests
    ttft = [r.token_t[0] - r.due for r in reqs if r.token_t]
    gaps = [b - a for r in reqs for a, b in zip(r.token_t, r.token_t[1:])]
    info = {
        "requests": len(reqs),
        "finished": sum(1 for r in reqs if _finished(r)),
        "tokens": sum(len(r.token_t) for r in reqs),
        "steps": len(run_.steps),
        "window_s": run_.window_s,
        "setup_s": run_.setup_s,
        "generator_late_max_s": late,
        "compiles_in_window": compiles,
        "check_s": check_s,
        "checked_requests": n_checked,
        "checked_tokens": n_tokens,
    }
    if "offered_rate" in window:
        info["offered_rate_per_s"] = window["offered_rate"]
    if ttft:
        # printed, not a metric: over a window's few dozen requests the
        # TTFT tail swings too far between runs to hold a bound
        info["ttft_p50_ms"] = statistics.median(ttft) * 1e3
        info["ttft_p95_ms"] = quantile(ttft, 0.95) * 1e3
    if gaps:
        info["itl_p50_ms"] = statistics.median(gaps) * 1e3
    log("info " + json.dumps(info))
