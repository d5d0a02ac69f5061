"""Mean share of the decode grid's slots that decoded, per engine step
that decoded, over the window (read from ``slot_req`` / ``slot_out``)."""


def read(run):
    dec = run.window.counters["decode"]
    if not dec:
        return None
    mb = run.cell.config["engine"]["max_batch"]
    return 100.0 * sum(n for _, n, _ in dec) / len(dec) / mb
