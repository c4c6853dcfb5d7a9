"""Seconds per request in the session's parse span (design resolution and its structural hash)."""


def read(run):
    return run.span_mean("parse")
