"""Readings that a cell's limit is set from: the program's and the
control's widest logit gap, on many seeds, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10

Each seed is one whole run of the cell (``harness.run``: weights from
the seed, warm-up, a window at the cell's own load, the check), with
the reference's int8 control run in lockstep beside the reference over
the same served requests. Prints one JSON line per seed: ``served_gap``
(the program against the reference: sound runs set the lower reading)
and ``control_gap`` (the control's first choices against the
reference: the upper reading).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE.parent / "src"))
    sys.path.insert(0, str(HERE))
    import harness
    import reference

    readings: dict = {}
    gaps = reference.Checker.gaps

    def with_control(self, prompts, served, control=False):
        readings.update(gaps(self, prompts, served, control=True))
        return readings

    reference.Checker.gaps = with_control
    for seed in (int(s) for s in args.seeds.split(",")):
        readings.clear()
        t = time.perf_counter()
        try:
            result = harness.run(args.workload, seed, args.seconds, False, t_start=t)
        except harness.NoChip as e:
            print(f"calibrate.py: {e}", file=sys.stderr)
            return 3
        print(json.dumps({
            "seed": seed, **readings, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "memory_peak_bytes": result["device"]["memory_peak_bytes"],
            "run_s": time.perf_counter() - t,
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
