"""Submit to result, per request: the first request's start to the last
request's end, over the requests completed in the window."""


def read(run):
    return run.window_s / len(run.done) if run.done else None
