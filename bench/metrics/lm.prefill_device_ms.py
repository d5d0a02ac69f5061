"""Device time of the prefill and first-token programs per admitted
request, in the traced window."""
from bench.metrics_common import LM_ADMIT


def read(run):
    n = len(run.window.traced["prompts"])
    if run.trace is None or not n:
        return None
    mods = run.trace.modules_named(LM_ADMIT)
    if not mods:
        return None
    return 1e3 * sum(m.end - m.start for m in mods) / 1e9 / n
