"""Process start to the first timed request: imports, the overlay, the
plan, and warming every program the cell's traffic can use."""


def read(run):
    return run.setup_s
