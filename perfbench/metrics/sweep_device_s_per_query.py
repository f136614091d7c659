"""Device time of the jitted FD sweep program in the traced window, per
answered query."""


def read(run):
    if run.trace is None or not run.trace["sweeps"] or not run.answered:
        return None
    return run.trace["sweep_device_s"] / len(run.answered)
