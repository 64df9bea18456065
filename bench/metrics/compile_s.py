"""Step compile time: the host span around ``lower().compile()`` of the
train step (an XLA compile, or a load from the persistent cache)."""


def read(run):
    return run.spans.total("compile")
