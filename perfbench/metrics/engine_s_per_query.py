"""Seconds the server spent inside the engine's ``run_many`` (host
draws, device sweeps, copies back, epilogue), per answered query, from
the benchmark's span around each call."""


def read(run):
    if run.trace is None or not run.answered:
        return None
    return sum(end - start for start, end in run.engine_calls) / len(
        run.answered)
