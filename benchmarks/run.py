"""Benchmark runner: one suite per paper figure/table + TPU comm models.

  PYTHONPATH=src python -m benchmarks.run [suite ...]

Prints ``name,value,derived`` CSV rows (the contract used by
EXPERIMENTS.md §Repro) and a per-suite wall time.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks.multi_query import ALL as MULTI
    from benchmarks.paper_figures import ALL as FIGS
    from benchmarks.tpu_comm import ALL as COMM
    suites = dict(FIGS)
    suites.update(COMM)
    suites.update(MULTI)
    want = sys.argv[1:] or list(suites)
    print("name,value,derived")
    for name in want:
        if name not in suites:
            print(f"# unknown suite {name}; have {sorted(suites)}",
                  file=sys.stderr)
            continue
        t0 = time.time()
        rows = suites[name]()
        for rname, val, derived in rows:
            sval = f"{val:.6g}" if isinstance(val, float) else str(val)
            print(f'{rname},{sval},"{derived}"')
        print(f"# suite {name}: {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
