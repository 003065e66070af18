"""The per-metric readers: ``metrics/<name>.py`` binds one of these as
``read(run) -> float | None``; ``None`` when the run holds nothing to
read, and the harness then leaves the metric out."""

import statistics

import costs
from harness import quantile


def bitlinear_roofline(run):
    """Kernels: the fused BitLinear kernel's share of its roofline in the
    profiled stretch. Work is that of the +-1 products the stretch's
    prefills and decode ticks needed (live rows only); time is the summed
    device time of the kernel's ops in the trace."""
    if run.trace is None:
        return None
    kernel_s = run.trace["kernel_s"].get("bitlinear", 0.0)
    rows = [p for s in run.traced_steps for p in s.prefills]
    rows += [len(s.decoded) for s in run.traced_steps if s.decoded]
    if kernel_s <= 0 or not rows:
        return None
    need_s, _ = costs.bitlinear_roofline(run.dims, rows, run.peak)
    return 100.0 * need_s / kernel_s


def decode_tick_ms_p50(run):
    """Slot-pool executor: median of the program's fenced ``decode_tick``
    spans (gather, decode and scatter of one tick) in the window."""
    spans = run.spans.get("decode_tick", [])
    return statistics.median(spans) * 1e3 if spans else None


def idle_share(run):
    """Device: the share of the profiled stretch in which no operation ran
    on the chip (1 minus the union of op intervals over the stretch)."""
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def itl_p95_ms(run):
    """95th percentile of every gap between consecutive tokens of every
    request due in the window (host clock)."""
    gaps = [b - a for r in run.requests for a, b in zip(r.token_t, r.token_t[1:])]
    return quantile(gaps, 0.95) * 1e3 if gaps else None


def mfu(run):
    """Whole step: the model's operations done in the window per second,
    over the chip's int8 peak (393 TOP/s on a v5e, which no implementation
    of +-1 products exceeds). Operations: every projection, attention over
    each token's live context, and the head once per token produced."""
    ops = 0.0
    for s in run.steps:
        ops += sum(costs.prefill_ops(run.dims, p) for p in s.prefills)
        ops += sum(costs.decode_ops(run.dims, p + i) for p, i in s.decoded)
    if ops <= 0:
        return None
    return 100.0 * ops / run.window_s / run.peak["int8_ops_per_s"]


def prefill_ms_p50(run):
    """Slot-pool executor: median of the program's fenced ``prefill`` spans
    (prompt prefill plus graft into the slot) in the window."""
    spans = run.spans.get("prefill", [])
    return statistics.median(spans) * 1e3 if spans else None


def setup_s(run):
    """Process start to the first timed request: loading, weights, the
    program's packing, and warming (or compiling) every shape."""
    return run.setup_s


def slot_occupancy(run):
    """Scheduler: slot-tokens decoded per decode tick over the pool's
    slots, from the program's ServingStats counters across the window."""
    ticks = run.stats1.ticks - run.stats0.ticks
    decoded = run.stats1.decoded - run.stats0.decoded
    return 100.0 * decoded / ticks / run.max_batch if ticks else None


def tokens_per_s(run):
    """Prompt tokens prefilled plus tokens generated inside the window,
    over the window's seconds (host clock): every step of the window,
    each prefill counting its prompt and its first token."""
    tokens = sum(sum(s.prefills) + len(s.prefills) + len(s.decoded) for s in run.steps)
    return tokens / run.window_s if tokens else None
