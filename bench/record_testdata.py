#!/usr/bin/env python3
"""Record the small chip trace that ``bench/tests/test_trace.py`` reduces.

    python bench/record_testdata.py --seed <n>

On the chip: the backlog cell's set-up, then a traced sub-window of about
one second around its dispatches (Pallas <8:8> ResNet-50 at bucket 16).
Writes ``bench/testdata/backlog.xplane.pb`` and, beside it,
``backlog.json``: the counters of the traced calls and what the reduction
read from the trace when it was recorded.
"""
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

from bench import harness  # noqa: E402

CELL = "resnet50-224.w8a8-backlog"


def main(argv=None) -> int:
    import argparse
    import json
    import shutil

    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--reduce-only", action="store_true",
                    help="reduce the committed trace again, with no chip")
    args = ap.parse_args(argv)
    out = harness.BENCH / "testdata"
    if args.reduce_only:
        with open(out / "backlog.json") as f:
            old = json.load(f)
        write(out, old["device_kind"], old["traced"], old["host_spans"])
        return 0
    cell = harness.load_cell(CELL)
    harness.compile_cache()
    try:
        devs = harness.accelerator(cell.chips)
    except harness.NoChip as e:
        print(f"record_testdata: {e}", file=sys.stderr)
        return 2
    drv = harness.driver_class(cell)(cell, args.seed)
    tmp = harness.ROOT / ".bench_trace"
    with jax.default_device(devs[0]):
        drv.setup()
        tracer = harness.Tracer(True, 4.0, tmp, host=drv.TRACE_HOST)
        tracer.offset, tracer.length = 1.0, 1.0
        win = drv.window(4.0, tracer)
        tracer.stop()
    shutil.copy(tracer.path(), out / "backlog.xplane.pb")
    write(out, devs[0].device_kind, win.traced, tracer.spans)
    return 0


def write(out, device_kind: str, traced: dict, host_spans: list) -> None:
    """``backlog.json``: the traced calls' counters and what the reduction
    reads from the trace."""
    import json

    from bench import trace
    from bench.metrics_common import PALLAS_OP

    tr = trace.load(str(out / "backlog.xplane.pb"), None, host_spans)
    record = {
        "device_kind": device_kind, "traced": traced,
        "host_spans": host_spans,
        "busy_s": tr.busy_s, "window_s": tr.window_s,
        "programs": len(tr.modules_named("")),
        "pallas_s": tr.op_seconds(tr.ops_matching(PALLAS_OP)),
        "pallas_ops": len(tr.ops_matching(PALLAS_OP)),
        "breakdown": tr.breakdown(),
    }
    with open(out / "backlog.json", "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record)[:3000])


if __name__ == "__main__":
    sys.exit(main())
