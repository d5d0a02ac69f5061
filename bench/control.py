#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python bench/control.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 3 --seconds <s>

For each seed, in one process: the cell's set-up and a window at its own
load, then the comparison with the plain reference (the program's
reading, printed as ``program``). For the first ``--control-seeds`` seeds
the control follows: the reference computed at the next lower precision
put in the program's place, compared with the same reference on the same
requests (printed as ``control``). The benchmark's own runs never run
the control. ``bench/limits/<cell>.json`` keeps the readings and the
limit set between them.
"""
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    import argparse
    import gc
    import json

    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.compile_cache()
    try:
        devs = harness.accelerator(cell.chips)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        drv = harness.driver_class(cell)(cell, seed)
        with jax.default_device(devs[0]):
            drv.setup()
            win = drv.window(args.seconds,
                             harness.Tracer(False, args.seconds, None))
            drv.free()
            gc.collect()
            out = {"seed": seed, "metrics": win.metrics,
                   "program": {c["name"]: c["value"] for c in drv.check()}}
            if i < args.control_seeds:
                out["control"] = drv.control()
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
        del drv
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
