#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> \
      --seconds <s> --trace <0|1>

The cells are the ``workloads`` of BENCHMARK.json, at the root of the
checkout. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``
(``breakdown`` with ``--trace 1``) and, last, ``checks``: each number of
the comparison with its limit, which also end standard error. A run that
finds no TPU, or fewer chips than the cell asks for, exits 3 and prints
no result.
"""
import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    try:
        sys.exit(harness.main(t_process=T_PROCESS))
    except harness.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        sys.exit(3)
