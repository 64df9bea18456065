"""Device idle share of the traced steps: one minus the union of the
device's operation intervals over the traced window, averaged over the
cell's devices."""


def read(run):
    if run.trace is None or run.trace["idle_share"] is None:
        return None
    return 100.0 * run.trace["idle_share"]
