#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip this machine holds.

    python3 benchmark/run.py --workload drift8v.steady --seed 7 \\
        --seconds 20 --trace 0

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its
reference, beside its limit. The same numbers are the last lines on
stderr. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
