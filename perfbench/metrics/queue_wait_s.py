"""Mean time an answered request waited in the server's queue before
its dispatch (the program's ``TopKResult.queue_s``)."""


def read(run):
    done = run.answered
    if not done:
        return None
    return sum(r.result.queue_s for r in done) / len(done)
