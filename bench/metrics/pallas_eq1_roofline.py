"""Roofline share of the Pallas Eq. 1 kernels in the vision forward.

The least time the chip needs for the Eq. 1 work of every traced dispatch
(2 m n k of each quantized conv and the fc, from the layer shapes in
``bench/work.py``; per GEMM the larger of its operations at the int8 peak
and its operand bytes, at the stated bit widths, at HBM bandwidth), over
the device time of the Pallas custom calls in the traced window.
"""
import sys

from bench import work
from bench.metrics_common import PALLAS_OP
from bench.traffic import bits


def read(run):
    b = bits(run.cell.traffic["precision"])
    if run.trace is None or b is None:
        return None
    t = run.trace.op_seconds(run.trace.ops_matching(PALLAS_OP))
    if t <= 0:
        return None
    cfg, pk = run.cell.config, work.peaks(run.device_kind)
    gemms = work.resnet50_gemms(cfg["image_size"], cfg["num_labels"])
    need, bounds = 0.0, {"compute": 0, "memory": 0}
    for batch in run.window.traced["batches"]:
        s, bd = work.eq1_roofline_s(gemms, batch, b, pk)
        need += s
        for k in bd:
            bounds[k] += bd[k]
    print(f"pallas_eq1_roofline: {len(run.window.traced['batches'])} "
          f"dispatches, Pallas {t!r} s, "
          f"roofline {need!r} s; GEMMs bound by {bounds}", file=sys.stderr)
    return 100.0 * need / t
