"""Seconds per request in the session's execute span (the forward, ending on host-side predictions)."""


def read(run):
    return run.span_mean("execute")
