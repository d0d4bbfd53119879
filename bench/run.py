"""The chip benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chip it is started on: sets up,
warms up, measures for ``--seconds``, checks the served tokens against the
plain reference, and prints one JSON object as the last line of standard
output. With ``--trace 0`` its metrics are the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read with the profiler on. It
exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for. ``--control 1`` checks the fp8 control's tokens in
the place of the served ones, and so has to print ``"correct": false`` (it
proves the check; the benchmark's own runs leave it off).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import NoChip, run_cell
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START,
                          control=bool(args.control))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
