"""Exposed collective time per step: device time of collective operations
(ring hops, reductions) during which no other operation runs on that
device, averaged over devices, per traced step.  Nothing to read where the
trace holds no collective."""


def read(run):
    if run.trace is None or not run.trace["collective_ops"]:
        return None
    return 1e3 * run.trace["collective_exposed_s"] / run.traced_steps
