"""Seconds per request in the session's plan span (features, partitioning, re-growth, routing)."""


def read(run):
    return run.span_mean("plan")
