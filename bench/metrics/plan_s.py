"""Planner time: the host span around ``auto_pipeline`` (partition,
schedule synthesis, lowering)."""


def read(run):
    return run.spans.total("plan")
