"""Mean query latency over every request of the window, each timed from
when it was due; a failed request counts as infinitely slow."""
import math


def read(run):
    lat = [r.latency_s for r in run.requests]
    if not lat:
        return None
    return math.fsum(lat) / len(lat)
