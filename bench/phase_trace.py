"""A traced run of one cell whose idle device time is put down to the host
phase that holds the chip back.

    python3 bench/phase_trace.py --workload <cell> --seed <n> --seconds <s>

Makes the run ``bench/run.py --trace 1`` makes, in this process, and prints
to standard error, where the run reduces its trace, two lines read from the
same trace (``bench/phases.py``):

* ``phase idle gaps``: the device's longest idle gaps, each named by the
  innermost benchmark (``bench.*``) or engine (``engine.*``, ``runner.*``)
  span open at its midpoint;
* ``phase idle split``: all of the window's idle seconds, split by the
  innermost span open at each instant.

The result line is ``bench/run.py``'s. With a program that records no
engine spans the names are the benchmark's own. ``--python-tracer 0``
turns off the profiler's tracing of every Python call, which slows the
host's Python-heavy phases while the profiler runs (the engine's spans are
recorded either way); the default, 1, traces as ``bench/run.py`` does.
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
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness, phases, trace

    reduce, start = trace.reduce_trace, jax.profiler.start_trace

    def reduce_and_name(pd, top: int = 10):
        print("phase idle gaps: " + json.dumps(phases.idle_gaps(pd, top)),
              file=sys.stderr)
        print("phase idle split: " + json.dumps(phases.idle_by_span(pd)),
              file=sys.stderr)
        return reduce(pd, top)

    trace.reduce_trace = reduce_and_name
    if not args.python_tracer:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace = lambda log_dir: start(
            log_dir, profiler_options=opts)
    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, True, T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    finally:
        trace.reduce_trace, jax.profiler.start_trace = reduce, start
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
