"""What the per-layer readers share: the names by which the trace shows
the engines' programs and the Pallas kernels, and small derived numbers."""
from __future__ import annotations

# The engines' jitted programs, by the name they are launched under.
LM_ADMIT = r"_prefill_impl|_admit_impl"
LM_DECODE = r"_decode_impl"
# Device operations that are Pallas (Mosaic) kernels.
PALLAS_OP = r"tpu_custom_call"


def decode_step_s(run):
    """Device seconds per decode step in the traced window."""
    steps = sum(s for s, _, _ in run.window.traced["decode"])
    if run.trace is None or not steps:
        return None
    mods = run.trace.modules_named(LM_DECODE)
    return (sum(m.end - m.start for m in mods) / 1e9 / steps) if mods \
        else None
