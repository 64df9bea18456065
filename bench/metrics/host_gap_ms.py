"""Training-loop time per step: mean host time from the start of a
step's batch to its dispatch returning (batch made on the host, placed,
step dispatched), over the window's steps.  The loop reads the loss of an
earlier step right before it, so this is the host's time from a loss read
to the next dispatch."""


def read(run):
    if not run.host_gaps:
        return None
    return 1e3 * sum(run.host_gaps) / len(run.host_gaps)
