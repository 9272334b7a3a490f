#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration, traffic mix, metrics) is found by name through
``BENCHMARK.json`` beside this directory.  Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.  The
last line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also ``breakdown``),
and, last, ``checks``: each number compared with the plain reference, beside
its limit.  The same checks are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

if __name__ == "__main__":
    import harness
    sys.exit(harness.main(sys.argv[1:], t_start=T_START, root=ROOT))
