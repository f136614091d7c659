"""Median query latency over every request of the window, each timed
from when it was due; a failed request counts as infinitely slow."""
from harness.traffic import percentile


def read(run):
    return percentile([r.latency_s for r in run.requests], 50)
