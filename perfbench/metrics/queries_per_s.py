"""Queries answered per second: every request sent in the window, over
the time from the window's open to its last answer."""


def read(run):
    done = run.answered
    if not done:
        return None
    return len(done) / max(r.done_s for r in done)
