#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate it sustains.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1,2,4,8

One process, one set-up; then one window per rate, in the order given.
For each rate it prints the requests due and finished, the latency
percentiles, and the mean latency of the first and the last third of the
arrivals: a backlog that grows through the window shows as a last third
far slower than the first. Run once when a cell is defined; the cell then
offers load at a fixed rate below the knee.
"""
import os
import sys
import time

T_START = time.perf_counter()
sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    import argparse
    import json

    import jax
    import numpy as np

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.compile_cache()
    try:
        devs = harness.accelerator(cell.chips)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    drv = harness.driver_class(cell)(cell, args.seed)
    with jax.default_device(devs[0]):
        drv.setup()
        print(f"sweep: set-up {time.perf_counter() - T_START:.1f} s",
              flush=True)
        for rate in map(float, args.rates.split(",")):
            off = harness.Tracer(False, args.seconds, None)
            win = drv.window(args.seconds, off, rate=rate)
            lat = np.asarray(drv.latencies)
            k = max(1, len(lat) // 3)
            print(json.dumps({
                "rate": rate, "due": win.attempted, "failed": win.failed,
                "metrics": win.metrics,
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "first_third_mean_ms": float(lat[:k].mean()),
                "last_third_mean_ms": float(lat[-k:].mean()),
                "late_s": win.counters.get("generator_late_s")}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
