"""Device time of the stem's 7x7/2 Eq. 1 conv kernel per traced dispatch.
The kernel is found by the name its op carries in the trace
(``eq1_conv_k7s2``, from the ``pallas_call``'s ``name``); a program whose
kernels carry no such name gives nothing to read."""
STEM = r"^%?eq1_conv_k7s2\b"


def read(run):
    n = run.window.traced["dispatches"]
    if run.trace is None or not n:
        return None
    ops = run.trace.ops_matching(STEM)
    if not ops:
        return None
    return 1e3 * run.trace.op_seconds(ops) / n
