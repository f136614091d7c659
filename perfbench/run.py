"""The benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Builds the cell's overlay, serves its traffic through the program's
``QueryServer`` for ``--seconds`` after warming every program the
traffic can use, checks a sample of the answers against the plain
reference, and prints one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, last, the
compared numbers beside their limits under ``check``.  Needs a TPU:
without one it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import cell  # noqa: E402

if __name__ == "__main__":
    sys.exit(cell.main(T_START))
