"""Queries answered per execution of the jitted sweep program in the
traced window: how full the batcher makes each device sweep."""


def read(run):
    if run.trace is None or not run.trace["sweeps"] or not run.answered:
        return None
    return len(run.answered) / run.trace["sweeps"]
