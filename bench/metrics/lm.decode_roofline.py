"""Roofline share of a decode step: the least time the chip needs for one
step (``bench/work.py``: every projection weight at the stated W bits, the
bfloat16 tied head, the norm scales and the live keys and values read
once, against the operations of the live slots at the int8 peak) over the
measured device time per decode step, both averaged over the traced
window."""
import sys

from bench import work
from bench.metrics_common import decode_step_s
from bench.traffic import bits


def read(run):
    t = decode_step_s(run)
    dec = run.window.traced["decode"]
    if t is None or not dec:
        return None
    cfg = run.cell.config
    b = bits(run.cell.traffic["precision"]) or (16, 16)
    steps = sum(s for s, _, _ in dec)
    kv = sum(k for _, _, k in dec) / steps
    slots = sum(s * n for s, n, _ in dec) / steps
    pk = work.peaks(run.device_kind)
    ops = slots * work.qwen3_token_ops(cfg, int(kv / max(slots, 1)))
    need, bound = work.roofline_s(ops, work.qwen3_decode_bytes(cfg, b[0], kv),
                                  pk["int8_ops_per_s"], pk["hbm_bytes_per_s"])
    print(f"lm.decode_roofline: {need!r} s per step ({bound} bound) over "
          f"{t!r} s measured; {slots!r} slots, {kv!r} live KV tokens",
          file=sys.stderr)
    return 100.0 * need / t
