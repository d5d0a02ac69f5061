"""Device time of the decode programs over the decode steps they ran, in
the traced window."""
from bench.metrics_common import decode_step_s


def read(run):
    s = decode_step_s(run)
    return None if s is None else 1e3 * s
