"""Find the knee of an open-loop cell: the highest offered rate the
served path sustains, by one sweep on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 4,6,8

One process sets the cell up once, then offers the mix's traffic at each
rate in turn for ``--seconds`` and drains it. Prints one JSON line per
rate: offered and completed rates, TTFT and token-gap percentiles, the
queue left waiting when the window closed and how long the drain took.
A rate is sustained while the queue at the close stays near empty and
the drain is short.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE.parent / "src"))
    sys.path.insert(0, str(HERE))
    import jax

    import harness
    import reference
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: no TPU; nothing was run", file=sys.stderr)
        return 3
    use_compile_cache()
    cell = harness.load_cell(args.workload)
    dims = reference.Dims.from_config(cell.config)
    se = harness.serve(cell, args.seed, dims)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        drv = harness.Driver(se, mix, args.seed, dims.vocab)
        t0 = time.perf_counter()
        window = harness.open_loop_window(drv, mix, args.seed, args.seconds, t0, None)
        close = time.perf_counter()
        waiting = sum(1 for r in window["requests"] if r.admitted is None)
        harness.drain(drv, window["requests"], t0 + args.seconds)
        drained = time.perf_counter() - close
        reqs = window["requests"]
        ttft = sorted(r.token_t[0] - r.due for r in reqs if r.token_t)
        gaps = sorted(b - a for r in reqs for a, b in zip(r.token_t, r.token_t[1:]))
        done_in_window = sum(1 for r in reqs if r.token_t and r.token_t[-1] <= close)
        print(json.dumps({
            "rate": rate,
            "offered_per_s": len(reqs) / args.seconds,
            "completed_in_window_per_s": done_in_window / args.seconds,
            "waiting_at_close": waiting,
            "drain_s": drained,
            "ttft_p50_ms": statistics.median(ttft) * 1e3,
            "ttft_p95_ms": harness.quantile(ttft, 0.95) * 1e3,
            "itl_p50_ms": statistics.median(gaps) * 1e3,
            "itl_p95_ms": harness.quantile(gaps, 0.95) * 1e3,
            "late_max_s": max(r.submitted - r.due for r in reqs),
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
