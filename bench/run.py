#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the cell's metrics as one JSON object on the last line of stdout,
and the numbers compared with the plain reference, each beside its limit,
as the last lines of stderr. Exits nonzero, printing no result, where JAX
finds no accelerator or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
# The TPU runtime logs to a fixed /tmp path unless told otherwise: keep
# its logs inside the checkout.
os.environ.setdefault("TPU_LOG_DIR", os.path.join(_ROOT, ".tpu_logs"))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
