"""Benchmark harness — one entry per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines."""
from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from . import (bench_alloc_overhead, bench_batchsize,
                   bench_fragmentation, bench_prefix_cache,
                   bench_spec_decode, bench_throughput, bench_vision_cache)
    benches = [
        ("fragmentation (paper §3.2 + Fig.16)", bench_fragmentation),
        ("decode batch size (Fig.15)", bench_batchsize),
        ("prefix caching (Fig.17)", bench_prefix_cache),
        ("alloc overhead / Llama parity (Fig.13)", bench_alloc_overhead),
        ("spec decode (Fig.19)", bench_spec_decode),
        ("vision cache (Fig.18)", bench_vision_cache),
        ("end-to-end engine throughput (Fig.13/14)", bench_throughput),
    ]
    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    for title, mod in benches:
        print(f"# --- {title}")
        try:
            mod.main(report=print)
        except Exception as e:  # keep the harness going; report the failure
            failures += 1
            print(f"{mod.__name__},-1,FAILED: {e!r}")
    print(f"# total_wall_s={time.time()-t0:.1f} failures={failures}")
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
