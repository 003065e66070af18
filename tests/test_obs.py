"""Telemetry subsystem (repro.obs): tracing spans, metrics, crosscheck.

Four contracts:

* **Recorder correctness** — spans nest (depth, completion order),
  carry attributes, honor an injected deterministic clock, and export
  valid JSONL / Chrome-trace / Prometheus text (golden outputs).
* **Off by default, no-op when off** — with no active session the
  module helpers return the shared ``NULL_SPAN`` (identity — no
  allocation), record nothing, and never touch the clock or the device.
* **Semantically invisible** — serving with tracing on produces
  byte-identical generations to serving with telemetry off, across the
  engine grid (the instrumentation's hard acceptance gate).
* **Crosscheck** — every traced decode tick pairs with a finite,
  positive modeled price per (engine, K), through the public
  ``CompiledModel.pricing_plan()`` seam.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro import compiler as compiler_lib
from repro import obs
from repro.compiler import HardwareTarget
from repro.configs import get_smoke_config
from repro.core import engine as engine_lib
from repro.models import lm as lm_lib
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_SPAN, Tracer

ENGINES = tuple(engine_lib.list_engines())


@pytest.fixture(autouse=True)
def _no_leaked_session():
    """Every test starts and ends with telemetry off."""
    obs.stop()
    yield
    obs.stop()


class FakeClock:
    """Deterministic ns clock: each read advances by ``step``."""

    def __init__(self, step: int = 1000):
        self.t = 0
        self.step = step

    def __call__(self) -> int:
        self.t += self.step
        return self.t


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_nesting_depth_and_order(self):
        tr = Tracer(clock=FakeClock())
        with tr.span("outer", track="t") as outer:
            with tr.span("inner", track="t") as inner:
                pass
        # completion order: child lands before parent
        assert [s.name for s in tr.spans()] == ["inner", "outer"]
        assert outer.depth == 0 and inner.depth == 1
        # fake clock: every read advances 1000ns, so durations are exact
        assert inner.duration_ns == 1000   # start read + end read
        assert outer.duration_ns == 3000   # spans inner's two reads
        assert outer.t_start_ns < inner.t_start_ns
        assert outer.t_end_ns > inner.t_end_ns

    def test_annotate_hook_wraps_each_span_nested(self):
        log = []

        class Mark:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name))

            def __exit__(self, *exc):
                log.append(("exit", self.name))

        tr = Tracer(clock=FakeClock(), annotate=Mark)
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        assert log == [("enter", "outer"), ("enter", "inner"),
                       ("exit", "inner"), ("exit", "outer")]
        assert [s.name for s in tr.spans()] == ["inner", "outer"]

    def test_span_attrs_entry_and_set(self):
        tr = Tracer(clock=FakeClock())
        with tr.span("tick", engine="wdm", k=4) as sp:
            sp.set(n_active=3)
        assert tr.spans("tick")[0].attrs == {
            "engine": "wdm", "k": 4, "n_active": 3,
        }

    def test_fence_blocks_device_work(self):
        tr = Tracer()
        x = jax.jit(lambda v: v * 2)(np.arange(8, dtype=np.float32))
        with tr.span("work") as sp:
            sp.fence(x)
        assert sp._fences == []          # drained at exit
        assert sp.duration_ns >= 0

    def test_events_and_filters(self):
        tr = Tracer(clock=FakeClock())
        tr.event("request.submit", track="sched", rid=7)
        with tr.span("tick"):
            pass
        assert len(tr.events()) == 1
        assert tr.events("request.submit")[0].attrs == {"rid": 7}
        assert tr.events("nope") == []
        assert [s.name for s in tr.spans("tick")] == ["tick"]

    def test_open_span_duration_raises(self):
        tr = Tracer()
        cm = tr.span("open")
        sp = cm.__enter__()
        with pytest.raises(ValueError, match="has not exited"):
            _ = sp.duration_ns
        cm.__exit__(None, None, None)

    def test_chrome_trace_golden(self):
        tr = Tracer(clock=FakeClock(step=500))
        with tr.span("compile", track="compile", engine="wdm"):
            pass
        tr.event("request.submit", track="sched", rid=0)
        doc = tr.to_chrome_trace()
        assert doc == {
            "traceEvents": [
                {"name": "thread_name", "ph": "M", "pid": 0, "tid": 0,
                 "args": {"name": "compile"}},
                {"name": "thread_name", "ph": "M", "pid": 0, "tid": 1,
                 "args": {"name": "sched"}},
                {"name": "compile", "ph": "X", "pid": 0, "tid": 0,
                 "ts": 0.5, "dur": 0.5, "args": {"engine": "wdm"}},
                {"name": "request.submit", "ph": "i", "s": "t", "pid": 0,
                 "tid": 1, "ts": 1.5, "args": {"rid": 0}},
            ],
            "displayTimeUnit": "ms",
        }

    def test_exports_round_trip(self, tmp_path):
        tr = Tracer(clock=FakeClock())
        with tr.span("tick", k=2):
            pass
        tr.event("mark")
        chrome = tmp_path / "trace.json"
        jsonl = tmp_path / "trace.jsonl"
        assert tr.export_chrome(str(chrome)) == 2
        assert tr.export_jsonl(str(jsonl)) == 2
        doc = json.loads(chrome.read_text())
        assert {e["ph"] for e in doc["traceEvents"]} == {"M", "X", "i"}
        rows = [json.loads(line) for line in jsonl.read_text().splitlines()]
        assert [r["type"] for r in rows] == ["span", "event"]
        assert rows[0]["attrs"] == {"k": 2}


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total", "things")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError, match="only go up"):
            c.inc(-1)

    def test_labeled_series_are_independent(self):
        reg = MetricsRegistry()
        c = reg.counter("ticks_total")
        c.labels(engine="wdm").inc(3)
        c.labels(engine="tiled").inc(4)
        assert c.labels(engine="wdm").value == 3
        assert c.value == 7   # family sum

    def test_gauge_set_inc_dec(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value == 6

    def test_histogram_bucket_math(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0))
        child = h.labels()
        for v in (0.5, 1.0, 1.5, 3.0, 100.0):
            child.observe(v)
        # per-bucket: <=1 gets 0.5 and 1.0; <=2 gets 1.5; <=4 gets 3.0;
        # 100.0 lands only in the implicit +Inf
        assert child.counts == [2, 1, 1]
        assert child.cumulative() == [2, 3, 4]
        assert child.total == 5
        assert child.sum == pytest.approx(106.0)
        assert child.mean == pytest.approx(21.2)
        assert child.quantile(0.5) == 2.0
        assert child.quantile(1.0) == float("inf")   # past the last bound
        assert child.quantile(0.0) == 1.0

    def test_histogram_validates_buckets(self):
        with pytest.raises(ValueError, match="sorted, unique"):
            MetricsRegistry().histogram("bad", buckets=(2.0, 1.0))
        with pytest.raises(ValueError, match="quantile"):
            MetricsRegistry().histogram("h").labels().quantile(1.5)

    def test_kind_conflict_is_an_error(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError, match="already registered as counter"):
            reg.gauge("x")

    def test_prometheus_render_golden(self):
        reg = MetricsRegistry()
        reg.counter("repro_ticks_total", "decode ticks").labels(
            engine="wdm"
        ).inc(3)
        reg.gauge("repro_depth", "queue depth").set(2)
        h = reg.histogram("repro_lat", "latency", buckets=(0.5, 1.0))
        h.labels(k=4).observe(0.25)
        h.labels(k=4).observe(2.0)
        assert reg.render() == (
            "# HELP repro_depth queue depth\n"
            "# TYPE repro_depth gauge\n"
            "repro_depth 2\n"
            "# HELP repro_lat latency\n"
            "# TYPE repro_lat histogram\n"
            'repro_lat_bucket{k="4",le="0.5"} 1\n'
            'repro_lat_bucket{k="4",le="1"} 1\n'
            'repro_lat_bucket{k="4",le="+Inf"} 2\n'
            'repro_lat_sum{k="4"} 2.25\n'
            'repro_lat_count{k="4"} 2\n'
            "# HELP repro_ticks_total decode ticks\n"
            "# TYPE repro_ticks_total counter\n"
            'repro_ticks_total{engine="wdm"} 3\n'
        )

    def test_export(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("x").inc()
        path = tmp_path / "metrics.txt"
        reg.export(str(path))
        assert path.read_text() == "# TYPE x counter\nx 1\n"


# ---------------------------------------------------------------------------
# session + disabled path
# ---------------------------------------------------------------------------


class TestSession:
    def test_off_by_default_helpers_are_noops(self):
        assert not obs.enabled() and obs.active() is None
        # identity: the one shared no-op span, no allocation per call
        assert obs.span("tick", engine="wdm") is NULL_SPAN
        assert obs.span("other") is NULL_SPAN
        with obs.span("tick") as sp:
            sp.set(k=4).fence(object())
        obs.event("x")
        obs.count("c", 2)
        obs.gauge_set("g", 1)
        obs.observe("h", 0.5)
        obs.cache_event("weight_cache", "hit")
        assert obs.active() is None   # nothing sprang into existence

    def test_start_stop_and_session_scope(self):
        tel = obs.start()
        assert obs.active() is tel and obs.enabled()
        with obs.span("tick"):
            pass
        assert len(tel.tracer.spans("tick")) == 1
        assert obs.stop() is tel
        assert not obs.enabled()
        with obs.session() as tel2:
            assert obs.active() is tel2
        assert not obs.enabled()

    def test_helpers_record_on_active_session(self):
        with obs.session() as tel:
            obs.count("repro_x_total", 2, engine="wdm")
            obs.gauge_set("repro_g", 7)
            obs.observe("repro_h", 0.1, buckets=(1.0,))
            obs.event("mark", rid=3)
        assert tel.metrics.counter("repro_x_total").value == 2
        assert tel.metrics.gauge("repro_g").value == 7
        assert tel.metrics.histogram("repro_h", buckets=(1.0,)).total == 1
        assert tel.tracer.events("mark")[0].attrs == {"rid": 3}

    def test_telemetry_write(self, tmp_path):
        with obs.session() as tel:
            with obs.span("tick"):
                pass
            obs.count("c")
        tel.write(
            trace_out=str(tmp_path / "t.json"),
            jsonl_out=str(tmp_path / "t.jsonl"),
            metrics_out=str(tmp_path / "m.txt"),
        )
        assert json.loads((tmp_path / "t.json").read_text())["traceEvents"]
        assert (tmp_path / "t.jsonl").read_text().count("\n") == 1
        assert "c 1" in (tmp_path / "m.txt").read_text()

    def test_disabled_overhead_loose_bound(self):
        # the gate is structural (no allocation / clock / sync creep),
        # with a CI-safe bound: 3 orders of magnitude above the real cost
        import time

        n = 10_000
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with obs.span("tick", track="serve", engine="none", k=1):
                pass
        per_call = (time.perf_counter_ns() - t0) / n
        assert per_call < 100_000, f"disabled span cost {per_call:.0f}ns/call"


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------


def _model():
    cfg = dataclasses.replace(get_smoke_config("tinyllama-1.1b"), quant="bnn")
    params = lm_lib.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, (3 + i,), np.int32) for i in range(3)
    ]
    return cfg, params, prompts


def _serve_tokens(cfg, params, prompts, target):
    from repro.serving import Request

    se = compiler_lib.compile(cfg, params, target).serve(max_batch=2, max_len=32)
    states = [
        se.submit(Request(rid=i, prompt=p, max_new_tokens=4))
        for i, p in enumerate(prompts)
    ]
    se.drain()
    return {st.rid: tuple(st.generated) for st in states}, se


@pytest.fixture(scope="module")
def model():
    return _model()


class TestServingIntegration:
    @pytest.mark.parametrize("engine", ENGINES)
    def test_tracing_is_bit_exact(self, model, engine):
        """The hard gate: telemetry must never change generated tokens,
        with or without the spans' profiler annotations."""
        cfg, params, prompts = model
        target = HardwareTarget(engine=engine, group_size=2)
        obs.stop()
        plain, _ = _serve_tokens(cfg, params, prompts, target)
        with obs.session() as tel:
            annotated, _ = _serve_tokens(cfg, params, prompts, target)
            tel.tracer = Tracer()   # the same session with unannotated spans
            traced, _ = _serve_tokens(cfg, params, prompts, target)
        assert traced == plain and annotated == plain and plain

    def test_compile_stage_spans(self, model):
        cfg, params, _ = model
        with obs.session() as tel:
            compiler_lib.compile(cfg, params, HardwareTarget(engine="wdm"))
        names = [s.name for s in tel.tracer.spans()]
        assert names == [
            "compile.validate", "compile.map", "compile.resolve",
            "compile.program", "compile",
        ]
        root = tel.tracer.spans("compile")[0]
        assert root.attrs["engine"] == "wdm"
        assert root.attrs["programmed"] > 0
        # stage spans nest under the root
        assert all(s.depth == 1 for s in tel.tracer.spans()[:-1])
        assert root.depth == 0

    def test_decode_tick_spans_and_metrics(self, model):
        cfg, params, prompts = model
        with obs.session() as tel:
            _, se = _serve_tokens(
                cfg, params, prompts, HardwareTarget(engine="wdm", group_size=2)
            )
        ticks = tel.tracer.spans("decode_tick")
        assert ticks and len(ticks) == se.stats().ticks
        for sp in ticks:
            assert sp.attrs["engine"] == "wdm"
            assert sp.attrs["k"] == se.group_k
            assert 1 <= sp.attrs["n_active"] <= se.max_batch
            assert sp.attrs["n_groups"] >= 1
            assert "cache_hits" in sp.attrs and "cache_misses" in sp.attrs
            assert sp.attrs["path"] in ("full", "masked", "gathered")
            assert sp.duration_ns > 0
        # the registry saw the same tick count and lane totals
        m = tel.metrics
        assert m.counter("repro_decode_ticks_total").value == len(ticks)
        assert (
            m.counter("repro_decoded_tokens_total").value
            == se.stats().decoded
        )
        assert m.counter("repro_mmm_groups_total").value == se.stats().mmm_groups
        assert m.histogram("repro_tick_latency_seconds").total == len(ticks)

    def test_request_lifecycle_events_and_histograms(self, model):
        cfg, params, prompts = model
        with obs.session() as tel:
            _, se = _serve_tokens(
                cfg, params, prompts, HardwareTarget(engine="wdm", group_size=2)
            )
        tr = tel.tracer
        n = len(prompts)
        assert len(tr.events("request.submit")) == n
        assert len(tr.events("request.admit")) == n
        assert len(tr.events("request.finish")) == n
        rids = {e.attrs["rid"] for e in tr.events("request.finish")}
        assert rids == set(range(n))
        m = tel.metrics
        for name in ("repro_ttft_seconds", "repro_queue_wait_seconds"):
            assert m.histogram(name).total == n
        latency = m.histogram("repro_request_latency_seconds")
        assert latency.labels(status="finished").total == n
        # host-clock seconds: a CPU smoke request takes well under 10 min
        for name in ("repro_ttft_seconds", "repro_queue_wait_seconds",
                     "repro_request_latency_seconds"):
            h = m.get(name)
            assert all(0.0 <= c.sum < 600.0 for c in h._series.values())
        assert m.get("repro_queue_depth_ticks") is None
        sch = se.scheduler.stats()
        assert m.gauge("repro_queue_depth").value == sch.queue_depth

    def test_cache_live_counters(self):
        # eager prepare_cached traffic mirrors into the live counter
        # (inside jit the cache is bypassed — tracer keys — so drive the
        # seam eagerly: first lookup misses, repeat hits)
        eng = engine_lib.get_engine("wdm")
        w = jax.numpy.asarray(
            np.where(
                np.random.default_rng(0).standard_normal((8, 8)) >= 0, 1, -1
            ),
            dtype=jax.numpy.float32,
        )
        with obs.session() as tel:
            eng.prepare_cached(w)
            eng.prepare_cached(w)
        c = tel.metrics.counter("repro_cache_events_total")
        assert c.labels(cache="weight_cache", kind="miss").value == 1
        assert c.labels(cache="weight_cache", kind="hit").value == 1
        assert eng.cache_stats()["weight_cache"]["hits"] == 1

    def test_prefill_spans(self, model):
        cfg, params, prompts = model
        with obs.session() as tel:
            _serve_tokens(
                cfg, params, prompts, HardwareTarget(engine="wdm", group_size=2)
            )
        pre = tel.tracer.spans("prefill")
        assert len(pre) == len(prompts)
        assert {sp.attrs["rid"] for sp in pre} == set(range(len(prompts)))
        assert all(sp.attrs["prompt_len"] == len(prompts[sp.attrs["rid"]])
                   for sp in pre)


class TestProgramScopes:
    """Named scopes put the program's parts into each HLO op's op_name,
    and the spans reach the profiler's host plane, nested."""

    COMMON = ("embed", "layers", "head", "norm", "attn.qkv", "attn.core",
              "attn.o", "ffn", "bitlinear")

    @pytest.fixture(scope="class")
    def served(self, model):
        cfg, params, _ = model
        return compiler_lib.compile(
            cfg, params, HardwareTarget(engine="packed")
        ).serve(max_batch=2, max_len=16)

    @pytest.mark.parametrize("program", ["decode", "gathered", "prefill"])
    def test_lowered_hlo_carries_scopes(self, served, program):
        import re

        jnp = jax.numpy
        se = served
        tok = jnp.zeros((2,), jnp.int32)
        fn, args, root, scopes = {
            "decode": (se._decode_full, (se.params, tok, tok, se.caches),
                       "decode", ("attn.kv_write",)),
            "gathered": (se._decode, (se.params, tok, tok, se.caches,
                                      jnp.zeros((1,), jnp.int32)),
                         "decode", ("attn.kv_write", "pool.gather", "pool.scatter")),
            "prefill": (se._prefill, (se.params, jnp.zeros((1, 4), jnp.int32)),
                        "prefill", ()),
        }[program]
        hlo = fn.lower(*args).compile().as_text()
        stacks = re.findall(r'op_name="([^"]*)"', hlo)
        parts = {p for st in stacks for p in st.split("/")}
        assert set(self.COMMON + scopes) <= parts
        # every scoped op sits under the program's root scope
        for name in self.COMMON + scopes:
            assert all(
                f"/{root}/" in st for st in stacks if f"/{name}/" in st
            ), name
        if program == "prefill":
            assert not parts & {"pool.gather", "pool.scatter", "attn.kv_write"}

    def test_spans_reach_the_profiler_host_plane_nested(self, model, tmp_path):
        from repro.serving import Request

        cfg, params, prompts = model
        se = compiler_lib.compile(
            cfg, params, HardwareTarget(engine="reference")
        ).serve(max_batch=2, max_len=32)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=3) for i, p in enumerate(prompts[:2])]
        # warm every program first so the profiled ticks hold no compiles
        for r in reqs:
            se.submit(r)
        se.drain()
        with obs.session():
            for r in reqs:
                se.submit(r)
            jax.profiler.start_trace(str(tmp_path))
            try:
                se.step()
                se.step()
            finally:
                jax.profiler.stop_trace()
        (path,) = list(tmp_path.rglob("*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(str(path))
        events = [
            (e.name, e.start_ns, e.end_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
        ]

        def named(n):
            return [e for e in events if e[0] == n]

        def inside(child, parents):
            return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)

        ticks, reads, admits = named("decode_tick"), named("decode.read"), named("sched.admit")
        assert len(ticks) == 2 and len(reads) == 2 and len(admits) == 2
        assert all(inside(r, ticks) for r in reads)
        assert all(inside(p, admits) for p in named("prefill"))
        assert len(named("prefill")) == 2
        # the tick runs under the scheduler's decode span, not its admission
        assert all(inside(t, named("sched.decode")) for t in ticks)
        assert not any(inside(t, admits) for t in ticks)
        # one scheduler span holds each step, its phases in it
        steps = named("sched.step")
        assert len(steps) == 2
        for phase in ("sched.expire", "sched.admit", "sched.collect", "sched.decode",
                      "sched.first_token"):
            assert len(named(phase)) == 2 and all(inside(e, steps) for e in named(phase))


# ---------------------------------------------------------------------------
# crosscheck
# ---------------------------------------------------------------------------


class TestCrosscheck:
    def test_crosscheck_serving(self, model):
        cfg, params, prompts = model
        with obs.session():
            _, se = _serve_tokens(
                cfg, params, prompts,
                HardwareTarget(engine="tiled", group_size=2),
            )
            rows = obs.crosscheck_serving(se)
        assert rows
        for r in rows:
            assert r.engine == "tiled"
            assert r.finite                       # finite and > 0
            assert r.ticks == se.stats().ticks
            assert r.modeled_ns > 0
            assert r.measured_total_ns >= r.measured_ns
        report = obs.format_report(rows)
        assert "tiled" in report and "ratio" in report

    def test_crosscheck_requires_session_or_tracer(self, model):
        cfg, params, prompts = model
        with obs.session() as tel:
            _, se = _serve_tokens(
                cfg, params, prompts,
                HardwareTarget(engine="tiled", group_size=2),
            )
        # session over: explicit tracer still works, no session raises
        assert obs.crosscheck_serving(se, tracer=tel.tracer)
        with pytest.raises(ValueError, match="no active telemetry session"):
            obs.crosscheck_serving(se)

    def test_pricing_plan_public_accessor(self, model):
        cfg, params, _ = model
        cm = compiler_lib.compile(cfg, params, HardwareTarget(engine="wdm"))
        plan = cm.pricing_plan()
        assert plan is cm.pricing_plan()   # memoized
        assert plan.n_tiles > 0

    def test_crosscheck_ticks_widths(self, model):
        """Partially-admitted ticks price at their own width, clamped to
        the pool; one row aggregates each (engine, K)."""
        cfg, params, _ = model
        cm = compiler_lib.compile(
            cfg, params, HardwareTarget(engine="tiled", group_size=2)
        )
        plan = cm.pricing_plan()
        tr = Tracer(clock=FakeClock())
        for width in (1, 2, 2):
            with tr.span("decode_tick", engine="tiled", k=2, n_active=width):
                pass
        rows = obs.crosscheck_ticks(tr, plan, pool=2)
        assert len(rows) == 1
        r = rows[0]
        assert (r.engine, r.k, r.ticks) == ("tiled", 2, 3)
        assert r.n_active_mean == pytest.approx(5 / 3)
        assert r.finite
