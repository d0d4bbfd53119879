"""Finds a rate cell's knee: serves its mix at several fixed rates in one
process and prints, for each, the end-to-end numbers and the backlog.

    python3 bench/sweep.py --workload <cell> --rates 0.5,1,2 --seconds 20

Each rate gets a fresh engine over the same weights, its own rehearsal and
warm-up, then the window. The knee is the highest rate at which the queue
of waiting requests does not grow over the window; the cell's mix file
holds 0.8 of it (PERF.md keeps the sweep). The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from jax import monitoring

    from bench.harness import CompileLog, NoChip, build_model, run_cell
    from bench.spec import Bench

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    model = build_model(bench.config(cell["config"]))
    clog = CompileLog()
    monitoring.register_event_duration_secs_listener(clog.listener)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = bench.mix(cell["traffic"])
        mix["arrival"]["rate_per_s"] = rate
        try:
            res = run_cell(ROOT, args.workload, args.seed, args.seconds,
                           False, time.perf_counter(), model=model,
                           clog=clog, mix=mix, check=False)
        except NoChip as e:
            print(f"sweep: {e}", file=sys.stderr)
            return 2
        print("rate", json.dumps({"rate_per_s": rate,
                                  "attempted": res["attempted"],
                                  "failed": res["failed"],
                                  **{k: v["value"] for k, v in
                                     res["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
