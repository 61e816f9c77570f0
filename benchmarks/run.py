"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Sections:
  - eqs/fig5/fig6/fig7/tab1: analytical model + DSE reproductions
  - tab2/fig8/fig9: PPA model reproductions
  - kernels/*: op microbenchmarks (CPU wall time)
  - roofline/*: the engine-backed bandwidth/roofline sweep
    (benchmarks.roofline_bench; the dry-run artifact table moved to
    ``python -m repro report``)
"""

from __future__ import annotations

import sys
import traceback

from repro._jax_compat import use_compile_cache


def main() -> None:
    from . import kernels_bench, paper_figs, roofline_bench

    benches = paper_figs.ALL + kernels_bench.ALL + roofline_bench.ALL
    print("name,us_per_call,derived")
    failures = 0
    for b in benches:
        try:
            for name, us, derived in b():
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{b.__name__},0,ERROR {type(e).__name__}: {e}", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    use_compile_cache()
    main()
